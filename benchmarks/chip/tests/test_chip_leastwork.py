"""Least-work counts against hand counts at ``dpsnn.reduced()`` (4x4
columns of 64 neurons, Gaussian stencil)."""
import dataclasses

import pytest

import leastwork
import reference


def _net(**kw):
    from repro.configs import dpsnn

    d = dataclasses.asdict(dpsnn.reduced(**kw))
    d.pop("name")
    return d


def test_stencil_and_index_width_by_hand():
    st = reference.stencil(_net())
    # 4 offsets at distance 1 (K = round(.02697 * 64) = 2), 4 diagonal
    # (round(.01455 * 64) = 1), 4 at distance 2 and 8 at (1, 2) (K = 1)
    assert len(st.offsets) == 20
    assert st.k_total == 4 * 2 + 4 + 4 + 8
    assert st.max_delay == 3
    # 64 neurons x 20 source columns = 1,280 addresses: two bytes
    assert leastwork.index_bytes(64, 20) == 2


def test_static_step_by_hand():
    net = _net()
    w = leastwork.step_work(net, k_total=24, n_offsets=20, steps=2,
                            d_events=50_000, d_spikes=100)
    neurons = 16 * 64
    ext = neurons * 540 * 3.0 * 1e-3 * 2        # expected drive arrivals
    recurrent = 50_000 - ext
    state = 2 * (2 * 4 + 1) + 1 / 8             # v, c, refrac r+w; spike bit
    assert w.step.bytes == pytest.approx(
        (recurrent * (4 + 2) + 2 * neurons * state) / 2)
    # the kernels deliver every recurrent event, the 100 * 24 remote ones
    # (the ELL delivery kernel) among them
    assert w.kernels.bytes == pytest.approx(
        (recurrent * (4 + 2) + 2 * neurons * state) / 2)
    assert w.step.flops == pytest.approx((50_000 + 10 * neurons * 2) / 2)
    assert w.kernels.flops == pytest.approx(
        (recurrent + 10 * neurons * 2 + neurons * 2) / 2)


def test_plastic_step_adds_touched_synapses_by_hand():
    net = _net(stdp=True)
    w = leastwork.step_work(net, k_total=24, n_offsets=20, steps=1,
                            d_events=30_000, d_spikes=100)
    neurons = 16 * 64
    recurrent = 30_000 - neurons * 540 * 3.0 * 1e-3
    local = recurrent - 100 * 24
    state = 2 * (2 * 4 + 1) + 1 / 8 + 2 * 2 * 4   # and both traces
    touched = 0.8 * (recurrent + 100 * (50 + 24))  # 50 = round(.8 * 63)
    touched_local = 0.8 * (local + 100 * 50)
    assert w.step.bytes == pytest.approx(
        recurrent * 6 + neurons * state + 2 * 4 * touched)
    # remote events are the kernels'; the remote rule (XLA) is not
    assert w.kernels.bytes == pytest.approx(
        recurrent * 6 + neurons * state + 2 * 4 * touched_local)
    assert w.kernels.flops == pytest.approx(
        recurrent + 10 * neurons + neurons + 4 * touched_local)


def test_least_time_names_its_bound():
    peak = leastwork.peaks("TPU v5 lite")
    t, bound = leastwork.least_time(leastwork.Work(1e9, 8.19e9), peak)
    assert bound == "memory" and t == pytest.approx(0.01)
    t, bound = leastwork.least_time(leastwork.Work(1.97e12, 1.0), peak)
    assert bound == "compute" and t == pytest.approx(0.01)


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        leastwork.peaks("TPU v9 imaginary")
