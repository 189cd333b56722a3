"""Device time of one execution of the serve-step program (the decode
step of every live row), mean over executions and devices."""
import numpy as np

import readings

PROGRAM = r"serve_step"


def read(ctx):
    calls = readings.program_calls(ctx, PROGRAM)
    return float(np.mean(calls)) * 1e3 if calls else None
