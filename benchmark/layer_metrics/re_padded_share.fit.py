"""Coordinate build + staging: over the fit's random-effect coordinates, the
share of the padded block cells that hold no real row, from the program's
own counters of each coordinate's build (`cells` and `padded_cells` of
`GameResult.coordinate_build`, the `train.re_build.<coordinate>.*` gauges),
which the builder carries in its `info`. Every pass over a bucket's blocks
and every `[E, S]` trial of its line search pays for the padded cells. A
count: it repeats exactly, on the CPU as on the chip."""
META = {"name": "re_padded_share.fit", "unit": "%",
        "layer": "Coordinate build + staging", "moves": "fit_examples_per_s"}


def read(record):
    coordinates = record["built"].get("coordinates")
    if not coordinates:
        return None             # an older commit: no such counters
    cells = sum(c["cells"] for c in coordinates.values())
    return 100.0 * sum(c["padded_cells"]
                       for c in coordinates.values()) / cells
