"""The benchmark's own tests run on the CPU: the harness's modules and the
program's sources go on the path; nothing here touches a chip."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT / "bench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
