"""Record the small device trace that ``test_trace.py`` reads.

Run on a TPU host from the checkout root:

    python3 bench/tests/record_trace.py bench/tests/fixtures/small_trace.xplane.pb

It traces a few calls of the two served programs whose events the per-layer
readers look for: the flat scan (``jit_flat_search``) and the HNSW layer-0
gather kernel (``beam_gather``), at small shapes, and prints every device
plane's lines with their distinct event names and stats, so the reader's
naming assumptions can be checked against the trace.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
from collections import Counter

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(out_path: str) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("no TPU: nothing recorded", file=sys.stderr)
        return 2
    from repro.core.flat import flat_search
    from repro.kernels import ops
    from repro.kernels.beam_gather import gather_table

    key = jax.random.key(0)
    corpus = jax.random.normal(key, (16384, 768), jnp.float32)
    queries = corpus[:8] + 0.01
    table = gather_table(corpus)
    ids = jax.random.randint(key, (8, 128), 0, 16384)

    def gather(q, i):
        return ops.beam_gather_distances(q, i, table, mode="dot")

    gathered = jax.jit(jax.vmap(gather))
    flat_search(queries, corpus, 10)[0].block_until_ready()
    gathered(queries, ids).block_until_ready()
    tmp = tempfile.mkdtemp(dir=os.environ.get("TMPDIR"))
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(3):
            flat_search(queries, corpus, 10)[0].block_until_ready()
            gathered(queries, ids).block_until_ready()
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True)
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        shutil.copy(path, out_path)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"trace: {os.path.getsize(out_path)} bytes -> {out_path}")
    data = jax.profiler.ProfileData.from_file(out_path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        if not plane.name.startswith("/device"):
            for line in lines:
                names = Counter(e.name for e in line.events)
                print(f"  LINE {line.name!r}: {names.most_common(8)}")
            continue
        for line in lines:
            events = list(line.events)
            names = Counter(e.name for e in events)
            print(f"  LINE {line.name!r} events={len(events)}")
            seen = set()
            for e in events:
                if e.name in seen:
                    continue
                seen.add(e.name)
                print(f"    {e.name!r} dur={e.duration_ns} "
                      f"n={names[e.name]} stats={dict(e.stats)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
