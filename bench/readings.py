"""Readings of the output check's numbers, for setting their limits.

    python bench/readings.py --workloads <cell>[,<cell>...] --seeds <n>,<n>,...
        [--control-seeds 3] [--seconds 3]

For each seed: one set-up of the cells' configuration (the cells must share
it), a short window of each cell's own traffic through the served path, and
the check's numbers of what it served (the lower readings).  On the first
``--control-seeds`` seeds also:

* the control: the configuration's reference recipe one step coarser
  (its ``control``: int4 codes for ``pca_affine``'s int8), put in the
  program's place — it answers the same sampled rows, and its answers are
  compared with the reference like the program's;
* for an IVF configuration, a routing fault planted in the program: the
  centroids permuted, so that every query probes the wrong lists.

Each reading is one JSON line on standard output; the last line gives, for
each number, the worst program reading, the least broken control and fault
readings, and the limit ``bench.check.set_limit`` sets between them;
``--write`` writes the limits into the configuration file.  Run on the
chip; the benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def control_numbers(ref, ref_low, q, k, names) -> dict:
    """The control's numbers: the coarser reference answers ``q`` and is
    compared with the configuration's reference."""
    from bench import check
    scores, ids = ref_low.topk(q, k)
    return check.compare(q, ids, scores, ref, k, names)


def propose_limits(lines: list[dict], names: list[str]) -> dict:
    """Per number: the worst sound reading, the least broken reading of
    each broken kind, and the limit ``check.set_limit`` puts between the
    sound one and the first broken kind three times away from it."""
    from bench import check
    out = {}
    for n in names:
        if n in ("lost", "malformed"):
            continue
        sound = [x[n] for x in lines if x["kind"] == "program" and n in x]
        entry = {"sound": max(sound), "broken": {}, "limit": None}
        for kind in ("control", "routing_fault"):
            vals = [x[n] for x in lines if x["kind"] == kind and n in x]
            if not vals:
                continue
            entry["broken"][kind] = min(vals)
            limit = check.set_limit(entry["sound"], min(vals))
            if limit is not None and entry["limit"] is None:
                entry["limit"] = float(f"{limit:.3g}")
                entry["from"] = kind
        out[n] = entry
    return out


def permute_centroids(engine, seed: int) -> None:
    """Routing fault: list ``l`` is reached through another list's
    centroid."""
    import jax.numpy as jnp
    import numpy as np
    index = engine.index
    perm = np.random.default_rng(seed).permutation(index.centroids.shape[0])
    index.centroids = jnp.asarray(index.centroids)[perm]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--write", action="store_true",
                    help="write the limits into the configuration file")
    args = ap.parse_args(argv)

    from bench.startup import configure_jax
    configure_jax()
    import numpy as np

    from bench import catalog, check, harness

    cells = [catalog.load_cell(n) for n in args.workloads.split(",")]
    if len({c.config["name"] for c in cells}) != 1:
        ap.error("the workloads must share one configuration")
    seeds = [int(s) for s in args.seeds.split(",")]
    names = list(cells[0].config["check"])
    ivf = "ivf" in cells[0].config["index"]
    lines = []

    def emit(**kw):
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        session = harness.Session(cells[0], seed, t_start=t0)
        served, faulted = {}, {}
        try:
            for cell in cells:
                session.warm(cell.traffic)
                run = session.window(args.seconds, False, t_start=t0,
                                     traffic=cell.traffic)
                lost = sum(r.error is not None for r in run.requests)
                served[cell.name] = (*session.checked_rows(run), lost)
            if ivf and i < args.control_seeds:
                permute_centroids(session.engine, seed)
                for cell in cells:
                    q = served[cell.name][0]
                    blocks = [session.service.query(
                        q[s: s + cell.traffic.rows], index=harness.INDEX_NAME,
                        k=cell.traffic.k) for s in range(
                            0, len(q), cell.traffic.rows)]
                    res = [b.result(timeout=600.0) for b in blocks]
                    faulted[cell.name] = (np.concatenate([r.ids for r in res]),
                                          np.concatenate([r.scores
                                                          for r in res]))
        finally:
            session.close()
        ref = session.reference()
        low = session.control() if i < args.control_seeds else None
        for cell in cells:
            q, ids, scores, lost = served[cell.name]
            k = cell.traffic.k
            values = check.compare(q, ids, scores, ref, k, names)
            values["lost"] = float(lost)
            emit(kind="program", seed=seed, workload=cell.name, **values)
            if low is not None:
                emit(kind="control", seed=seed, workload=cell.name,
                     **control_numbers(ref, low, q, k, names))
            if cell.name in faulted:
                f_ids, f_scores = faulted[cell.name]
                emit(kind="routing_fault", seed=seed, workload=cell.name,
                     **check.compare(q, f_ids, f_scores, ref, k, names))
        del ref, low
        print(f"[readings] seed {seed} done in "
              f"{time.perf_counter() - t0:.1f} s", file=sys.stderr, flush=True)

    limits = propose_limits(lines, names)
    print(json.dumps({"limits": limits, "seeds": seeds}), flush=True)
    if args.write:
        path = ROOT / cells[0].config_entry["file"]
        config = json.loads(path.read_text())
        config["check"].update({n: v["limit"] for n, v in limits.items()
                                if v["limit"] is not None})
        path.write_text(json.dumps(config, indent=2) + "\n")
        print(f"[readings] limits written to {path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
