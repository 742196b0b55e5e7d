"""wave_roofline: the least time the wave programs could take, as
a share of their device time in the trace.  The least time is a bytes
bound: the operand planes read once and the result planes written once
over each call's real lanes, at the chip's HBM bandwidth.  There is no
compute bound: the VPU's integer rate of the chip is not published."""


# `pim/scheduler.py:_wave_runner`'s jitted `body`, as the trace names it
WAVE_PROGRAM = "jit_body"


def read(r):
    if r.trace is None or not r.window.total("min_bytes"):
        return None
    device_s = r.trace.modules.get(WAVE_PROGRAM, 0.0)
    if device_s <= 0:
        return None
    least_s = r.window.total("min_bytes") / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
