import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from chipbench import harness  # noqa: E402

BENCH = harness.load_benchmark()


def one_cell_per_mix():
    """A cell on ``2nn-er128`` for each traffic mix of ``traffic/`` (the
    configurations differ in scale only, which the tiny cells replace)."""
    return sorted(f"{p.stem}.2nn-er128"
                  for p in (harness.HERE / "traffic").glob("*.json"))


def full_size_mode(cell) -> str:
    """The mode the trainer resolves to at the cell's own size."""
    from repro.core.baselines import make_scheduler
    from repro.core.runner import choose_mode
    from repro.scenarios import get_scenario
    from repro.xp.builders import build_graph

    cfg, trf = cell.config, cell.traffic
    if trf["mode"] != "auto":
        return trf["mode"]
    n = cfg["n_workers"]
    sched = make_scheduler(
        trf["algorithm"], build_graph(cfg["topology"], n, p=cfg["edge_prob"],
                                      seed=cfg["graph_seed"]),
        get_scenario(trf["scenario"], n=n, **trf.get("scenario_kw", {})),
        **trf.get("scheduler_kw", {}))
    return choose_mode(n, sched.active_buckets(), sched.global_events)


def tiny_cell(name, n=32, d_in=64):
    """The cell at N=n, d_in=d_in, 64-event runs, on the mode and the
    limits of the full-size cell (N=32 puts DSGD-AAU on a two-rung ladder)."""
    mix, config = name.split(".", 1)
    bench = dict(BENCH, workloads=[{"name": name, "config": config,
                                    "traffic": mix, "chips": 1}])
    cell = harness.load_cell(bench, name)
    mode = full_size_mode(cell)
    cell.config = dict(cell.config, n_workers=n, d_in=d_in,
                       samples_per_worker=50000 // n)
    cell.traffic = dict(cell.traffic, events_per_run=64, eval_every=32,
                        mode=mode)
    return cell


@pytest.fixture
def peak():
    import json
    return json.loads((harness.HERE / "peaks.json").read_text())["TPU v5 lite"]
