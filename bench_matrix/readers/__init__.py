"""One module per kind of reader. A reader is `read(args, env)`: `args` is
the metric file's `args`, `env` a `ReadEnv`. It returns the value, or None
when what it reads is not there (the harness then leaves the metric out)."""

from __future__ import annotations

from dataclasses import dataclass


@dataclass
class ReadEnv:
    cell: dict
    samples: dict  # what the runner counted
    trace: object  # reduce.xplane.Trace, or None without a traced slice
    peaks: dict  # the device's row of peaks.json
    chips: int
    memory_peak_bytes: int
    say: callable
