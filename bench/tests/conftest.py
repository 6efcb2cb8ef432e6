"""The harness's own tests: CPU only, small sizes.

    python -m pytest bench/tests

They are not among the repository's tier-1 tests (``testpaths`` is
``tests``).  ``small`` cuts a cell to a size the CPU runs in seconds, through
the same discovery and code as a run on the chip.
"""

import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import spec  # noqa: E402

# rows at which a 1% filter still leaves more than k=100 rows
SMALL_ROWS = 12000


@pytest.fixture
def small(monkeypatch):
    """Cells at SMALL_ROWS rows, 64 pooled queries, 8 clients."""
    real = spec.load_json

    def load(kind, name):
        d = real(kind, name)
        if kind == "configs":
            d.update(rows=SMALL_ROWS, queries=64)
            if "hnsw" in d["vector"]:
                d["vector"] = dict(d["vector"], hnsw=dict(
                    d["vector"]["hnsw"], build_batch=1024))
        elif kind == "traffic":
            d.update(clients=8, processes=2)
        return d

    monkeypatch.setattr(spec, "load_json", load)
    return load
