"""Benchmark harness: one module per paper table/figure (+ kernels, DSE).

Prints ``name,us_per_call,derived`` CSV by default, as required.
``--json`` instead emits one machine-readable JSON document (a list of
``{"name", "us_per_call", "derived"}`` rows) so CI can diff benchmark
output across PRs; ``--out FILE`` writes it to a file as well.
Paper-claims benchmarks print the reproduced number next to the paper's
measured value.

``--out`` refuses to overwrite an existing file whose JSON schema it
does not recognize (anything that is not a row list) — the trajectory
files the individual benchmarks own (see :data:`TRAJECTORY_FILES`)
carry a different row schema, and a mistyped ``--out BENCH_dse.json``
used to silently clobber them.  Pass ``--force`` to overwrite anyway.

**Trajectory files**: each ``BENCH_*.json`` is a JSON *list* of
timestamped snapshot rows (newest last) — one row appended per benchmark
run via :func:`append_bench_row` — so the perf trajectory accretes
across PRs instead of being overwritten.  Each benchmark used to write a
single bare snapshot dict, so every run *replaced* the previous numbers
and the "trajectory tracked across PRs" the docstrings promised never
existed; :func:`load_trajectory` still reads those legacy single-dict
documents as one-row trajectories, and regression guards compare against
:func:`latest_row`.
"""
import argparse
import json
import os
import sys
import tempfile
from datetime import datetime, timezone

# `python benchmarks/run.py` puts benchmarks/ (not the repo root) on
# sys.path; add the root so `from benchmarks import ...` resolves both
# there and under `python -m benchmarks.run`.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

ROW_KEYS = {"name", "us_per_call", "derived"}

# The trajectory files the individual benchmarks own (append-only row
# lists, newest last).  This is the canonical schema constant: the
# static-analysis gate (``python -m repro.analysis --bench``) reads it
# to assert every file exists and its latest row still passes the
# enforced gates recorded inside it, so a regressed append cannot land
# silently.  Add new ``BENCH_*.json`` files HERE, not just in the
# benchmark module that writes them.
TRAJECTORY_FILES = ("BENCH_dse.json", "BENCH_sim.json",
                    "BENCH_sim_batch.json", "BENCH_sim_faults.json",
                    "BENCH_observe.json", "BENCH_shard.json")


def is_row_list(doc) -> bool:
    """True iff ``doc`` is this harness's own output schema: a list of
    row dicts each carrying exactly the ``ROW_KEYS`` channels."""
    return (isinstance(doc, list)
            and all(isinstance(r, dict) and set(r) == ROW_KEYS
                    for r in doc))


def _warn(msg):
    print(f"benchmarks/run.py: {msg}", file=sys.stderr)


def _salvage_rows(text):
    """Recover the complete row objects from a corrupt (typically
    truncated mid-write) trajectory document.

    Walks the text with ``JSONDecoder.raw_decode`` from the opening
    ``[``, collecting every complete dict until the first undecodable
    span — a half-written trailing row is dropped, everything before it
    survives.
    """
    dec = json.JSONDecoder()
    i = text.find("[")
    if i < 0:
        return []
    i += 1
    rows = []
    n = len(text)
    while True:
        while i < n and text[i] in " \t\r\n,]":
            i += 1
        if i >= n:
            break
        try:
            obj, i = dec.raw_decode(text, i)
        except ValueError:
            break
        if isinstance(obj, dict):
            rows.append(obj)
    return rows


def load_trajectory(path):
    """Read a ``BENCH_*.json`` trajectory as a list of snapshot rows.

    Missing/empty files read as an empty trajectory; a legacy bare-dict
    snapshot (the pre-trajectory schema) reads as a one-row trajectory
    so old committed files keep their history when the next run appends
    to them.  A corrupt/partially-written file does NOT read as empty —
    that used to silently drop the whole history on the next append —
    instead the complete leading rows are salvaged (and malformed
    non-dict rows skipped) with a warning on stderr.
    """
    try:
        with open(path) as f:
            text = f.read()
    except OSError:
        return []
    if not text.strip():
        return []
    try:
        doc = json.loads(text)
    except ValueError:
        rows = _salvage_rows(text)
        _warn(f"{path}: corrupt/partially-written trajectory; salvaged "
              f"{len(rows)} complete row(s), skipping the rest")
        return rows
    if isinstance(doc, dict):
        return [doc]
    if isinstance(doc, list):
        good = [r for r in doc if isinstance(r, dict)]
        if len(good) != len(doc):
            _warn(f"{path}: skipped {len(doc) - len(good)} malformed "
                  "(non-dict) trajectory row(s)")
        return good
    _warn(f"{path}: unrecognized trajectory schema "
          f"({type(doc).__name__}); reading as empty")
    return []


def latest_row(path):
    """The most recent snapshot row of a trajectory file (or ``None``).

    Regression guards compare against this instead of ``json.load``-ing
    the file as a dict — the read that silently broke once the files
    became row lists.
    """
    rows = load_trajectory(path)
    return rows[-1] if rows else None


def _write_trajectory(path, rows):
    """Write a trajectory atomically: serialize to a temp file in the
    same directory, then ``os.replace`` over the target.  A crash (or a
    concurrent reader) mid-write can no longer leave a truncated file
    in place of the whole history."""
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(
        dir=d, prefix=os.path.basename(path) + ".", suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(rows, f, indent=2)
            f.write("\n")
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def append_bench_row(path, snapshot):
    """Append one snapshot row (stamped ``recorded_utc``) to ``path``.

    Returns the full trajectory after the append.  This is the only
    writer the individual benchmarks use — replacing the ``json.dump``
    of a bare dict that used to overwrite the whole history each run.
    The write is atomic (temp file + rename).
    """
    rows = load_trajectory(path)
    row = dict(snapshot)
    row.setdefault("recorded_utc",
                   datetime.now(timezone.utc).isoformat(timespec="seconds"))
    rows.append(row)
    _write_trajectory(path, rows)
    return rows


def amend_latest_row(path, extra):
    """Merge ``extra`` keys into the newest row of a trajectory file.

    For multi-part benchmarks (``bench_dse``) whose later sections fold
    stats into the snapshot the earlier section just appended — an amend
    of the current run's row, never a new row.  Atomic like
    :func:`append_bench_row`.
    """
    rows = load_trajectory(path)
    assert rows, f"amend_latest_row({path!r}): no trajectory to amend"
    rows[-1].update(extra)
    _write_trajectory(path, rows)
    return rows


def check_out_target(path, *, force: bool = False) -> None:
    """Refuse to clobber an existing ``--out`` file we did not write.

    A missing file, an empty file, or a previous row-list emission are
    fine; any other schema (e.g. the ``BENCH_*.json`` trajectory files,
    whose snapshot rows carry benchmark-specific keys rather than exactly
    ``ROW_KEYS``) raises ``SystemExit`` unless ``force``.  Runs BEFORE
    the benchmarks so a bad target fails in milliseconds, not after
    minutes of measurement.
    """
    if force or path is None or not os.path.exists(path):
        return
    with open(path) as f:
        text = f.read()
    if not text.strip():
        return
    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
    if not is_row_list(doc):
        raise SystemExit(
            f"refusing to overwrite {path}: existing file is not a "
            f"benchmark row list (keys {sorted(ROW_KEYS)}); it looks like "
            "a file owned by another writer (e.g. a BENCH_*.json "
            "trajectory document). Pass --force to overwrite anyway.")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="emit a JSON row list instead of CSV")
    ap.add_argument("--out", default=None,
                    help="also write the (JSON) output to this file")
    ap.add_argument("--force", action="store_true",
                    help="overwrite --out even if its schema is foreign")
    args = ap.parse_args(argv)
    check_out_target(args.out, force=args.force)
    from repro.shard import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_contention, bench_dfs_traffic, bench_dse,
                            bench_kernels, bench_observe, bench_replication,
                            bench_shard, bench_sim, bench_sim_batch,
                            bench_sim_faults)
    mods = [("replication(TableI)", bench_replication),
            ("contention(Fig3)", bench_contention),
            ("dfs_traffic(Fig4)", bench_dfs_traffic),
            ("dse", bench_dse),
            ("sim(closed-loop)", bench_sim),
            ("sim_batch(multi-design)", bench_sim_batch),
            ("sim_faults(robustness)", bench_sim_faults),
            ("observe(monitoring)", bench_observe),
            ("shard(multi-device)", bench_shard),
            ("kernels", bench_kernels)]
    rows = []
    failures = 0
    for label, mod in mods:
        try:
            for name, us, derived in mod.run():
                rows.append({"name": name, "us_per_call": round(us, 1),
                             "derived": derived})
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{label},0,ERROR:{e!r}", file=sys.stderr)

    if args.json:
        doc = json.dumps(rows, indent=2)
        print(doc)
        if args.out:
            with open(args.out, "w") as f:
                f.write(doc + "\n")
    else:
        print("name,us_per_call,derived")
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
