"""Device time of the BSR kernel's calls inside one decode step, mean."""
from bench.devtrace import DECODE_PROGRAM


def read(run):
    if run.device is None:
        return None
    n = len(run.device.program_times(DECODE_PROGRAM))
    return run.device.kernel_time(DECODE_PROGRAM) / n * 1e3 if n else None
