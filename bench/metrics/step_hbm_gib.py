"""Bytes the compiled train step needs on its fullest device, in GiB:
``memory_analysis()`` arguments + outputs - aliased + temp."""


def read(run):
    return run.step_hbm_bytes / 2**30
