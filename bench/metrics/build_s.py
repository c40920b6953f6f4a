"""Seconds of HybridIndex.build, host clock, ended by block_until_ready."""


def read(run):
    return run.build_s
