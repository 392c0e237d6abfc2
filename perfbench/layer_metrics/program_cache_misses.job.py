"""Fusion engine: programs the fusion cache had to build inside the window
(`fusion.program_cache().stats()["misses"]`, end minus start). Expected 0."""


def read(run):
    return run.counter_delta("program_cache_misses")
