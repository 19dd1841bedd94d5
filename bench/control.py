#!/usr/bin/env python3
"""Runs of a cell with a broken path in the program's place, to show
that the comparison deciding ``correct`` fails it.  Not part of the
benchmark's own runs.

    python3 bench/control.py --fault <name> --workload <cell> --seed <n> --seconds <s>

Faults, each put in place of the engine's executor (its documented
wrapping hook), so the window's submit, batching and slot mapping run
as they do in a benchmark run:

* ``control``: the reference computed per RNS prime in float64 and
  joined by CRT (``reference.control_multiply``), in the program's
  place; it breaks the configuration's guarantee of an exact product;
* ``alter``: the program's answer in slot 0 of every dispatch with one
  coefficient changed where it is produced;
* ``half``: the upper half of every dispatch's slots left uncomputed.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import reference, run  # noqa: E402


def control(executor, cfg):
    v, w, L, primes = cfg["v"], cfg["limb_bits"], cfg["limb_count"], cfg["q_primes"]

    def execute(pl, za, zb):
        out = np.zeros((za.shape[0], za.shape[1], L), np.int64)
        for r in range(za.shape[0]):
            if za[r].any() and zb[r].any():
                c = reference.control_multiply(reference.digits_to_ints(za[r], v),
                                               reference.digits_to_ints(zb[r], v), primes)
                out[r] = reference.ints_to_digits(c, w, L)
        return out

    return execute


def alter(executor, cfg):
    def execute(pl, za, zb):
        out = np.array(executor(pl, za, zb))
        out[0, 0, 0] ^= 1
        return out

    return execute


def half(executor, cfg):
    def execute(pl, za, zb):
        out = np.array(executor(pl, za, zb))
        out[out.shape[0] // 2:] = 0
        return out

    return execute


FAULTS = {"control": control, "alter": alter, "half": half}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    args, rest = ap.parse_known_args(argv)
    return run.main(rest, wrap_executor=FAULTS[args.fault])


if __name__ == "__main__":
    sys.exit(main())
