"""Plain float32 references of the benchmark's training jobs.

Written from the published descriptions and the configuration files in
``bench/configs``, in straightforward ``jax.numpy``; they import nothing
of the program (``repro``) and take nothing it made.  Departures of the
program from the published models are listed in each configuration's
``assumed``; the reference follows the program there, so that what is
compared is the program's arithmetic, not its choice of model.
"""
