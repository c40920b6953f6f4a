"""The slowest chip's device time per search run over the chips' mean
(``shards.ms_per_search``): 1.0 is an even split of the work."""

import shards


def read(run):
    if run.trace is None:
        return None
    per_chip = list(shards.ms_per_search(run.trace).values())
    if not per_chip:
        return None
    return max(per_chip) / (sum(per_chip) / len(per_chip))
