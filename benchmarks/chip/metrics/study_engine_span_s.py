"""study_engine_span_s: seconds per study in the window spent in the
study engine: the time covered by the program's spans ``papas.study.*``
(plan, schedule, record), less the ``papas.gang.*`` spans nested in
them. Moves ``study_makespan_s``."""
from benchmarks.chip import scopes


def read(ctx):
    found = scopes.program_study_spans(ctx)
    return None if found is None else found["engine_span_s"]
