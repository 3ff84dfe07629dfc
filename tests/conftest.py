import os
import sys

# The suite runs on the CPU, also on a machine with a TPU: set before any
# test module imports JAX, so that no pytest worker can take the chip.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# tests must see the single real CPU device (the dry-run's 512-device
# override is process-local to repro.launch.dryrun runs)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
