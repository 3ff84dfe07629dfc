"""``batch_occupancy`` (as ``batch_occupancy.py`` reads it) in the cells judged on latency."""
import runpy
from pathlib import Path

read = runpy.run_path(str(Path(__file__).with_name("batch_occupancy.py")))["read"]
