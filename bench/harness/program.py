"""The system under test, as the benchmark reaches it: the checkout's
`src/` on the import path.  Each driver imports the entry points it calls
(`drivers/`); the runner imports the program's spans, and `run.py` its
compile cache."""
from __future__ import annotations

import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[2] / "src"


def on_path() -> None:
    """Raises ImportError where the checkout holds no program (only the
    benchmark's own files)."""
    if not (SRC / "repro").is_dir():
        raise ImportError(f"no program under {SRC}")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
