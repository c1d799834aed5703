"""What every thread of the run waited for the two recorders' own
locks (``TraceBuffer._lock``: a request's spans, roots and flushes;
``FlightRecorder._lock``: a dispatch's record), in ms, total since the
process started: small in a sound run, seconds where the handler
threads convoy on the span buffer. Read from the program's counters
when the line is written."""


def read(r):
    from predictionio_tpu.utils import device_telemetry, tracing

    spans = getattr(tracing.trace_buffer(), "lock_stats", None)
    ring = device_telemetry.recorder().counts().get("lockWaitedUs")
    if spans is None or ring is None:
        return None
    return (spans()["waitedUs"] + ring) / 1e3
