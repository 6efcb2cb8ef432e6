"""Layer-0 traversal trips per query: over the traced window's
``engine.device`` spans of the HNSW pass (``pass`` = ``hnsw``), the sum of
their ``trips`` over the sum of their real (unpadded) ``queries``."""

from bench import spans


def read(run):
    passes = [s for s in spans.named(spans.of(run) or [], "engine.device")
              if s.args.get("pass") == "hnsw"]
    queries = sum(s.args["queries"] for s in passes)
    if queries <= 0:
        return None
    return sum(s.args["trips"] for s in passes) / queries
