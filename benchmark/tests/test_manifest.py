import glob
import json
import os

import pytest

from benchmark.families.vilbert import flops
from benchmark.harness.spec import BENCH_DIR, ROOT, Spec, reader_file
from benchmark.reduce import readers

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
# Every configuration file that is kept, a cell's or one for a later cell.
CONFIG_FILES = sorted(glob.glob(os.path.join(BENCH_DIR, "configs", "*.json")))


def family_of(path):
    with open(path) as f:
        return json.load(f)["family"]


VILBERT_FILES = [p for p in CONFIG_FILES if family_of(p) == "vilbert"]


def reporters(metric):
    return set(metric.get("workloads", CELLS))


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_moves_names_an_end_to_end_metric_all_its_cells_report(metric):
    (target,) = [m for m in MANIFEST["end_to_end"]
                 if m["name"] == metric["moves"]]
    assert reporters(metric) <= reporters(target)
    with open(reader_file(metric["name"])) as f:
        assert callable(readers.find_kind(json.load(f)["kind"]))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_its_files_and_reports_enough(cell):
    spec = Spec(cell)
    names = [m["name"] for m in spec.end_to_end()]
    assert "setup_s" in names and len(names) >= 2
    assert spec.per_layer()
    assert spec.traffic["arrivals"] in ("open", "closed")
    spec.family.check_traffic(spec.config, spec.traffic)
    assert os.path.exists(spec.find(
        "tests", f"tiny.{spec.config['family']}.json"))


def test_every_listed_configuration_is_a_kept_file():
    assert {os.path.join(ROOT, c["file"])
            for c in MANIFEST["configs"]} <= set(CONFIG_FILES)


@pytest.mark.parametrize("path", CONFIG_FILES, ids=os.path.basename)
def test_every_configuration_names_a_family_that_is_there(path):
    with open(path) as f:
        config = json.load(f)
    family = Spec(CELLS[0]).family_of(config)
    for name in ("assets", "schedule", "weights", "boot", "units_since",
                 "flops_per_unit", "unwritten_bytes", "sample",
                 "run_reference", "compare", "frame_of", "check_traffic"):
        assert callable(getattr(family, name)), name
    with pytest.raises(SystemExit, match="names no \"family\""):
        Spec(CELLS[0]).family_of({k: v for k, v in config.items()
                                  if k != "family"})


@pytest.mark.parametrize("path", VILBERT_FILES, ids=os.path.basename)
def test_flop_count_equals_the_programs_today(path):
    from vilbert_multitask_tpu.config import EngineConfig, ViLBertConfig
    from vilbert_multitask_tpu.engine.flops import serving_forward_flops

    with open(path) as f:
        config = json.load(f)
    model = {k: tuple(v) if isinstance(v, list) else v
             for k, v in config["model"].items()}
    mine = flops.forward_flops_per_row(config["model"], config["engine"])
    assert mine == Spec(CELLS[0]).family_of(config).flops_per_unit(config)
    theirs = serving_forward_flops(ViLBertConfig(**model), EngineConfig(), 1)
    assert mine == theirs


@pytest.mark.parametrize("path", VILBERT_FILES, ids=os.path.basename)
def test_weight_tree_is_the_one_the_program_serves(path):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import vilbert
    from vilbert_multitask_tpu.config import ViLBertConfig
    from vilbert_multitask_tpu.models.vilbert import ViLBertForVLTasks

    with open(path) as f:
        config = json.load(f)
    model = {k: tuple(v) if isinstance(v, list) else v
             for k, v in config["model"].items()}
    module = ViLBertForVLTasks(ViLBertConfig(**model))
    e = config["engine"]

    def init(rng):
        z = lambda *s: jnp.zeros(s, jnp.int32)
        return module.init(
            rng, z(2, e["max_text_len"]),
            jnp.zeros((2, e["max_regions"], model["v_feature_size"])),
            jnp.zeros((2, e["max_regions"], 5)), z(2, e["max_text_len"]),
            z(2, e["max_text_len"]), z(2, e["max_regions"]), None, z(2, 1),
            deterministic=True)["params"]

    theirs = jax.tree_util.tree_map(
        lambda x: tuple(x.shape),
        jax.eval_shape(init, jax.random.PRNGKey(0)))
    assert vilbert.param_shapes(config["model"]) == theirs
