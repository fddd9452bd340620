"""Device milliseconds per execution of the program ``args["module"]``, from
the ``XLA Modules`` line of the busiest chip in the newest profiler trace.
None when the trace has no such program (a CPU run; a commit that names its
programs otherwise)."""

from benchmark.lib import spans as S


def read(observations: dict, args: dict):
    found = S.per_execution(S.load()["modules"], args["module"])
    if found is None:
        return None
    ns, executions = found
    return ns / executions / 1e6
