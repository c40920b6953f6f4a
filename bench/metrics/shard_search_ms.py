"""Device milliseconds of one run of the search program on the slowest
chip, each chip's time counted over that chip's own runs
(``shards.ms_per_search``)."""

import shards


def read(run):
    if run.trace is None:
        return None
    per_chip = shards.ms_per_search(run.trace)
    return max(per_chip.values()) if per_chip else None
