"""study_engine_s: seconds per study in the window spent in the PaPaS
sweep outside the gang call: the wall of ``sweep.main`` less the wall
of ``train/ensemble.py:train_ensemble``, both timed by the benchmark's
own spans. Moves ``study_makespan_s``."""


def read(ctx):
    return ctx.get("study_engine_s")
