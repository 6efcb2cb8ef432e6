"""Read a planted fault's numbers on the chip, at the cell's own size.

Run on a TPU host from the checkout root:

    python3 bench/tests/read_fault.py --workload hnsw100k-search \\
        --fault one_trip ef_to_k --seconds 10 --seeds 11 12 13

For each fault and seed it runs the cell as ``bench/run.py`` does, in
this one process, with the fault of ``faults.py`` planted under the timed
path, and prints one line per run with every compared number beside its
limit.  The last line is a JSON object with each fault's numbers by seed.
These readings are the upper ends from which ``bench/limits`` are set; the
benchmark's own runs do not plant anything.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    sys.path.insert(0, str(p))

import pytest  # noqa: E402

from bench import run  # noqa: E402
from bench.tests import faults  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, nargs="+",
                    choices=sorted(faults.FAULTS))
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    out = {}
    for fault, seed in [(f, s) for f in args.fault for s in args.seeds]:
        buf = io.StringIO()
        with pytest.MonkeyPatch.context() as mp, \
                contextlib.redirect_stdout(buf):
            faults.plant(mp, fault)
            run.run(["--workload", args.workload, "--seed", str(seed),
                     "--seconds", str(args.seconds), "--trace", "0"])
        lines = buf.getvalue().strip().splitlines()
        result = json.loads(lines[-1])
        numbers = [ln for ln in lines if ln.startswith("[check] reference")]
        print(f"[fault] {args.workload} {fault} seed {seed}: correct "
              f"{result['correct']}; "
              + " ".join(f"{n} {v['value']!r} (limit {v['limit']!r})"
                         for n, v in result["checks"].items()), flush=True)
        print("   ", numbers[0] if numbers else "", flush=True)
        out.setdefault(fault, {})[str(seed)] = {
            n: v["value"] for n, v in result["checks"].items()}
    print(json.dumps({"workload": args.workload, "numbers": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
