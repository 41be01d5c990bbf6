"""Host work after each chunk and after a run's last one, in
milliseconds a run: the device idle charged to ``scan.drain``,
``scan.overflow``, ``scan.traces`` and ``scan.finish``, over the
``sim.run`` spans that lie wholly inside the traced window."""
from bench import program_spans


def read(trace, counts, peak):
    return program_spans.idle_ms_per_run(trace, program_spans.EPILOGUE)
