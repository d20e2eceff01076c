"""Plain references that decide a benchmark run's ``correct``.

``selftimed`` states the model plainly on the configuration's JSON form:
MRB substitution (Algorithm 1), the tasks of one firing with their
communication times (Eq. 11), and the self-timed execution and its period.
``search`` builds on it: the relaxed decode, the NSGA-II ranking and the
device variation operators.  ``verifier`` and the types it reads
(``graph``, ``architecture``, ``mrb``, ``schedule``) are a frozen copy of
the program's independent schedule verifier at the commit that defined
the benchmark, with imports made local.  Nothing here imports the program.
"""
