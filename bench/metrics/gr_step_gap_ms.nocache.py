"""``gr_step_gap_ms`` in the cells that report ``gr_p99_ms``: the same reader."""

import runpy
from pathlib import Path

read = runpy.run_path(str(Path(__file__).with_name("gr_step_gap_ms.py")))["read"]
