"""Device milliseconds per run of a program, in the operations the program
traced under a scope: `args.program` is a regex on the program's name on
the `XLA Modules` line without its number (`^jit_step$`), `args.scope` a
regex on the operation's scope path with the wrappers of transformations
peeled (`(^|/)kv_gather(/|$)`), or null for every operation of the program
(shorter than the program's own events by the seams between operations).
Runs are counted on the modules line, an operation belongs to a program by
its `program_id`; mean over the cell's devices. See `reduce/scopes.py` for
what a path is and for the limit of booking a fusion to one component.

`ReadEnv` carries the loaded `Trace` but not its file, so the file is found
where `run.main` puts the trace of a `--trace 1` run; without one there
(no traced run, or a test tracing elsewhere) the value is None, as it is
when the program did not run in the slice or has no such scope.

The metrics that name this reader wait in `layer_metrics_queued/` until a
`benchmark` PR lists them in the cells' files; until then
`python3 -m bench_matrix.reduce.scopes .bench_matrix_out/trace/<cell>` reads
them from the trace a `--trace 1` run leaves.
"""

import os
import time

from ..reduce import scopes, xplane

# trace file -> Scopes: one parse and one printed table a process, however
# many metrics read the trace
_PARSED = {}


def _scopes(env):
    from .. import run

    name = env.cell.get("name")
    if env.trace is None or not name:
        return None
    try:
        path = xplane.find(os.path.join(run.OUT_DIR, "trace", name))
    except FileNotFoundError:
        return None
    if path not in _PARSED:
        _PARSED.clear()
        t0 = time.perf_counter()
        _PARSED[path] = sc = scopes.read(path)
        env.say(f"device time by program and component ({path} read a second "
                f"time, for the scope paths, in {time.perf_counter() - t0:.2f} s):\n"
                + scopes.table(sc))
    return _PARSED[path]


def read(args, env):
    sc = _scopes(env)
    if sc is None:
        return None
    return scopes.time_in(sc, args["program"], args.get("scope"))
