"""The control: the reference, one precision down, in the program's place.

    python3 bench/control.py --workload <name> --seeds 11 12 13

For each seed it draws the cell's corpus and query pool as a run does,
answers every pooled query with the plain reference computed at
``precision="high"`` (three-pass bfloat16, the step below the float32 the
configurations state), and hands those answers to the same comparison a run
uses (``check.compare``).  The comparison has to refuse them: each seed's
numbers are printed beside the cell's limits, and the last line is a JSON
object with every seed's numbers.  The benchmark's own runs do not run it;
``bench/tests/test_control.py`` keeps it at a small size.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import check, data, reference, spec  # noqa: E402
from bench.run import allowed_rows, filter_of, load_cell  # noqa: E402


def control_numbers(cfg, traffic, seed: int,
                    precision: str = "high") -> Dict[str, float]:
    corpus, queries = data.draw(seed, cfg["rows"], cfg["queries"],
                                cfg["dim"], cfg["data"])
    flt = filter_of(traffic, cfg["rows"])
    allowed = allowed_rows(flt, cfg["rows"])
    k = traffic["k"]
    top_ids, _ = reference.exact_topk(corpus, queries, k, allowed)
    kth = reference.kth_sims(corpus, queries, top_ids)
    ids, sims = reference.exact_topk(corpus, queries, k, allowed,
                                     precision=precision)
    answers = {"ok": np.ones(len(queries), bool),
               "query": np.arange(len(queries)),
               "ids": ids, "scores": 1.0 - sims.astype(np.float64)}
    return check.compare(answers, corpus, queries, top_ids, kth, allowed, k)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    _, cfg, traffic, limits = load_cell(spec.benchmark(), args.workload)
    out = {}
    for seed in args.seeds:
        t0 = time.monotonic()
        numbers = control_numbers(cfg, traffic, seed)
        judged = check.judge(numbers, limits)
        print(f"[control] {args.workload} seed {seed} "
              f"({time.monotonic() - t0:.1f}s): "
              + " ".join(f"{n} {v['value']!r} (limit {v['limit']!r})"
                         for n, v in judged.items())
              + f"; refused: {not check.passed(judged)}", flush=True)
        out[str(seed)] = numbers
    print(json.dumps({"workload": args.workload, "numbers": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
