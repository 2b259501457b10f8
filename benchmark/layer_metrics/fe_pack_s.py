"""Coordinate build + staging: host seconds the fixed-effect coordinate
spent packing its sparse shard for the device (the padded rows and the
column-sorted view), from the program's own counter: `pack_s` of
`GameResult.coordinate_build`, the `train.fe_build.<coordinate>.pack_s`
gauge, as the FIRST fit of the process reported it (the builder carries it
in its `info`; the pack runs once a dataset, in set-up)."""
META = {"name": "fe_pack_s", "unit": "s",
        "layer": "Coordinate build + staging", "moves": "setup_s"}


def read(record):
    built = record["built"].get("fe_build")
    if not built:
        return None             # an older commit: no such counter
    return built["pack_s"]
