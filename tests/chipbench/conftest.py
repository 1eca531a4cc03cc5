"""The benchmark's own tests: its harness modules import as `harness.*`
from `chipbench/`, as `chipbench/run.py` imports them."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[2] / "chipbench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
