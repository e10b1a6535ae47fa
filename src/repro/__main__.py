"""``python -m repro`` — one dispatcher for every workload CLI.

Usage::

    python -m repro train  --arch yi-6b --smoke --rounds 5
    python -m repro serve  --arch yi-6b --smoke --steps 16
    python -m repro dryrun --arch mamba2-780m --shape train_4k
    python -m repro fl     --model mobilenet --rounds 10
    python -m repro sweep  run roofline-all-archs
    python -m repro analyze --preset ci-tiny --fail-on error

Each subcommand is a thin CLI over :class:`repro.api.Session` (``sweep``
drives grids of them through :mod:`repro.sweep`); the installed console
scripts (``repro-train``, ``repro-serve``, ``repro-dryrun``, ``repro-fl``,
``repro-sweep``) map to the same entry points.
"""

from __future__ import annotations

import sys

_COMMANDS = {
    "train": "repro.launch.train",
    "serve": "repro.launch.serve",
    "dryrun": "repro.launch.dryrun",
    "fl": "repro.launch.fl",
    "sweep": "repro.sweep.cli",
    "analyze": "repro.analyze.cli",
}


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    cmd, rest = argv[0], argv[1:]
    if cmd not in _COMMANDS:
        print(f"unknown command {cmd!r}; options: {', '.join(_COMMANDS)}",
              file=sys.stderr)
        return 2
    # import late: each CLI defers jax (and any XLA_FLAGS it adds) to main
    import importlib

    mod = importlib.import_module(_COMMANDS[cmd])
    rc = mod.main(rest)
    # launcher mains return run artifacts (history dicts); only int is a code
    return rc if isinstance(rc, int) else 0


if __name__ == "__main__":
    sys.exit(main())
