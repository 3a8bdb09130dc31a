"""Device time of a phase that the program names itself.

The program puts its phases under ``repro.obs.scope`` and says, for an
instruction of a compiled program it registered, which phases hold it
(``repro.obs.phase_of``).  A phase's time is the summed device time of the
ops it holds, loops left out (a ``while`` carries its body's phase, and
its body's ops are on the same line), per step of the window.  A program
without ``repro.obs``, or a trace in which no op maps to the phase, reads
nothing.
"""
from chipbench import trace


def ms_per_step(m, phase: str):
    try:
        from repro import obs
    except ImportError:
        return None
    steps = m["rec"]["steps"]
    dev = m["trace"].op_seconds(
        lambda o: o.label not in trace.CONTAINERS
        and phase in obs.phase_of(o.module, o.name))
    if not steps or dev <= 0:
        return None
    return 1000.0 * dev / steps
