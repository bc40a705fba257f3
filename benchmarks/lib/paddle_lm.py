"""Where benchmarks/models/opt.py stood before PR 33. Kept for
tests/test_program_names.py alone, a file outside the benchmark's paths
that a ``benchmark`` PR may not edit: it reads ``make_weights``,
``build_trainer`` and ``rows_of`` here. The PR that points that test at
``manifest.load_module("models", "opt")`` deletes this file (PERF.md,
Open questions). Nothing of the benchmark imports it.
"""

from benchmarks.lib import manifest

_opt = manifest.load_module("models", "opt")
make_weights = _opt.make_weights
build_trainer = _opt.build_trainer
rows_of = _opt.rows_of
