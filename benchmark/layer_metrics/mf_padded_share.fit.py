"""Coordinate build + staging: of what the factored random effect's update
reads a pass, the share that trains nothing, from the program's own counters
of it (`GameResult.coordinate_build[<coordinate>]["mf_build"]`, the
`train.mf_build.<coordinate>.*` gauges, which the builder carries in its
`info`): `cells` of the per-entity blocks the latent half solves on and
`rows` of the design the projection's refit reads, against `padded_cells`,
the cells that hold no row and the rows at weight 0. A count: it repeats
exactly, on the CPU as on the chip. A commit with no such counters reads
nothing."""
META = {"name": "mf_padded_share.fit", "unit": "%",
        "layer": "Coordinate build + staging", "moves": "fit_examples_per_s"}


def read(record):
    built = [c["mf_build"] for c in
             (record["built"].get("coordinates") or {}).values()
             if "mf_build" in c]
    if not built:
        return None
    return 100.0 * sum(b["padded_cells"] for b in built) / sum(
        b["cells"] + b["rows"] for b in built)
