"""Process start to the first timed request: data, ``build_index`` with
its calibration, and the warm-up (host clock)."""


def read(run):
    return run.setup_s
