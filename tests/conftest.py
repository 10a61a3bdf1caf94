import os
import sys

# Tests run single-device (the dry-run alone forces 512 host devices, in
# its own process). Make sure nothing leaks in from the environment.
os.environ.pop("XLA_FLAGS", None)
# The persistent compile cache stays off under test, in this process and
# in every worker a test spawns (runtime/compile_cache.py).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
