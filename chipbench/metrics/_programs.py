"""The program's device programs, by their jit names, in a trace."""
DECODE = "jit_decode_step"
PREFILL = "jit_prefill"


def durations_ns(run, name):
    """Device durations of every run of program ``name`` on device 0."""
    if run.trace is None:
        return []
    return [e - s for _, s, e in run.trace.module_events(name)]
