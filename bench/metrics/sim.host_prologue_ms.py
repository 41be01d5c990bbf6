"""Host work before a run's first chunk, in milliseconds a run: the
device idle charged to ``sim.run`` outside its children, ``sim.reset``,
``scan.setup`` and ``scan.to_device``, over the ``sim.run`` spans that
lie wholly inside the traced window."""
from bench import program_spans


def read(trace, counts, peak):
    return program_spans.idle_ms_per_run(trace, program_spans.PROLOGUE)
