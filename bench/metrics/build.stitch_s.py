"""Seconds of the bulk HNSW build's stitch phase, as the builder times it on
the host (``build_s_stitch`` in the collection's stats after the build)."""


def read(run):
    value = run.build_info.get("build_s_stitch")
    return None if value is None else float(value)
