import os
import sys

# tests run single-device (multi-device tests set their own device count
# in SUBPROCESSES; setting it here would poison every other test's jit cache)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
