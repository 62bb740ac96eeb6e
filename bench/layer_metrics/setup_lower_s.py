"""Set-up: seconds the process spent tracing programs to jaxprs and
lowering them to MLIR, from the first engine build to the window start,
read from the program's compile log (``telemetry.compile_log``; a
trace nested in another counts once). These run in every process,
before the persistent compilation cache can answer. Read only in a run
whose trace holds the chip's plane: a run without the chip does not
set up the cell's programs."""
from bench.layer_metrics._common import device


def read(obs):
    from repro.runtime import telemetry

    log = getattr(telemetry, "compile_log", None)
    if log is None or device(obs) is None:
        return None
    return log().seconds(("trace", "lower"), obs.t0)
