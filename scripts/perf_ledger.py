"""Perf-ledger CLI: read, append to, and gate on a perf ledger file.

The file is ``--path`` or ``$VMT_PERF_LEDGER``; with neither, reads are
empty and appends write nothing (the repo's PERF_LEDGER.jsonl belongs to
the benchmark driver, not to this program).

The ledger (obs/ledger.py) is the append-only sequence of headline
numbers every bench/soak/smoke run leaves behind — one JSON line per run,
stamped with wall time, git rev, and config fingerprint. This CLI is the
operator/CI face:

    python scripts/perf_ledger.py show [--metric M] [--last N]
    python scripts/perf_ledger.py check [--metric M] [--window 5]
        [--tolerance 0.20] [--tolerate-empty]
    python scripts/perf_ledger.py append METRIC key=value [key=value ...]

``check`` compares the NEWEST run of each metric against the median of up
to ``--window`` prior runs, per comparable key (direction inferred from
the key name: ``*_ms`` lower-is-better, ``*qps``/``speedup`` higher), and
exits 0 on pass, 1 on regress, 2 on usage/IO error. A fresh checkout has
no ledger and a young one has no baseline window — ``--tolerate-empty``
maps the ``empty`` and ``no-baseline`` verdicts to exit 0 so CI can gate
unconditionally while the trajectory accumulates.

``append`` exists for ad-hoc runs (a hand-timed TPU window, a one-off
measurement) so they enter the same trajectory as scripted runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from vilbert_multitask_tpu.obs import ledger  # noqa: E402


def _parse_kv(pairs) -> dict:
    out = {}
    for pair in pairs:
        if "=" not in pair:
            raise SystemExit(f"expected key=value, got {pair!r}")
        k, v = pair.split("=", 1)
        try:
            out[k] = json.loads(v)  # numbers stay numbers, strings need no quotes
        except ValueError:
            out[k] = v
    return out


def cmd_show(args) -> int:
    entries = ledger.read_entries(args.path, metric=args.metric)
    for e in entries[-args.last:] if args.last else entries:
        print(json.dumps(e, sort_keys=True))
    if not entries:
        print(f"# ledger empty: {args.path or ledger.default_ledger_path()}",
              file=sys.stderr)
    return 0


def cmd_check(args) -> int:
    result = ledger.check(args.path, metric=args.metric,
                          window=args.window, tolerance=args.tolerance)
    print(json.dumps(result, indent=2))
    verdict = result["verdict"]
    if verdict == "pass":
        return 0
    if verdict in ("empty", "no-baseline"):
        if args.tolerate_empty:
            print(f"# verdict {verdict}: tolerated (no baseline yet)",
                  file=sys.stderr)
            return 0
        print(f"# verdict {verdict}: ledger has no gateable baseline "
              "(--tolerate-empty to accept)", file=sys.stderr)
        return 2
    for r in result["regressions"]:
        print(f"# REGRESS {r['metric']}.{r['key']}: {r['value']} vs "
              f"baseline {r['baseline']} ({r['direction']} is better, "
              f"{r['delta_frac'] * 100:+.1f}% worse, "
              f"n={r['n_baseline']})", file=sys.stderr)
    return 1


def cmd_append(args) -> int:
    values = _parse_kv(args.values)
    # CLI appends (check.sh's lint-wall entry) stamp the default config's
    # fingerprint: baselines must never mix entries from different configs
    # under a null fingerprint.
    from vilbert_multitask_tpu.config import (
        FrameworkConfig,
        config_fingerprint,
    )

    entry = ledger.append_entry(
        args.metric, values, path=args.path,
        config_fingerprint=config_fingerprint(FrameworkConfig()))
    print(json.dumps(entry, sort_keys=True))
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--path", default=None,
                   help="ledger file (default: $VMT_PERF_LEDGER; with "
                        "neither, nothing is read or written)")
    sub = p.add_subparsers(dest="cmd", required=True)

    s = sub.add_parser("show", help="print entries, oldest first")
    s.add_argument("--metric", default=None)
    s.add_argument("--last", type=int, default=0,
                   help="only the newest N entries")
    s.set_defaults(fn=cmd_show)

    c = sub.add_parser("check", help="regression verdict vs trailing window")
    c.add_argument("--metric", default=None,
                   help="gate one metric only (default: all)")
    c.add_argument("--window", type=int, default=5,
                   help="baseline = median of up to N prior runs")
    c.add_argument("--tolerance", type=float, default=0.20,
                   help="relative noise bound before a key counts as "
                        "regressed")
    c.add_argument("--tolerate-empty", action="store_true",
                   help="exit 0 on empty/no-baseline ledgers (CI bootstrap)")
    c.set_defaults(fn=cmd_check)

    a = sub.add_parser("append", help="hand-append one entry")
    a.add_argument("metric")
    a.add_argument("values", nargs="+", metavar="key=value")
    a.set_defaults(fn=cmd_append)

    args = p.parse_args(argv)
    try:
        return args.fn(args)
    except OSError as e:
        print(f"# perf_ledger: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
