"""The harness finds every piece of a cell by name, and a later PR adds a
configuration with its own env and networks, a mix and a metric by
adding files and entries only."""
import json
import math
import os
import re
import shutil
import subprocess
import sys

import compare
from catalog import Catalog

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_resolves():
    cat = Catalog()
    for cell in cat.spec["workloads"]:
        got, cfg, mix, limits = cat.workload(cell["name"])
        assert got is cell
        assert cfg["source"] == next(c["source"] for c in cat.spec["configs"]
                                     if c["name"] == cell["config"])
        assert limits and set(limits) <= set(compare.NAMES)
        assert mix["batch_size"] > 0
    for group in ("end_to_end", "per_layer"):
        for cell in cat.spec["workloads"]:
            assert cat.metrics(group, cell)
    for m in cat.spec["per_layer"]:
        assert callable(cat.reader(m["name"]))


def test_benchmark_json_shape():
    spec = Catalog().spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(spec)) < 64 * 1024
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in spec[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for group, want in keys.items():
        for x in spec[group]:
            assert set(x) - {"workloads"} == want, x
    for w in spec["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert {m["name"] for m in spec["end_to_end"]} >= {"setup_s"}
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert all(m["moves"] in e2e for m in spec["per_layer"])


FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
#: run in the copy: the dummy cell's reference at a tiny size, and the
#: two count-reading metrics on a made-up traced window
FOLLOW_DUMMY = """
import json, sys
sys.path.insert(0, "bench")
import compare, counts
from catalog import Catalog
from reference import pipeline
cat = Catalog(".")
cell, cfg, mix, _ = cat.workload("dummy_cfg.dummy_mix")
cfg = dict(cfg, replay_capacity=256, hidden=[32, 32])
mix = dict(mix, num_envs=4, chunk_len=8, batch_size=16,
           updates_per_round=2, rounds_per_dispatch=2, warmup_frames=64)
ref = pipeline.follow(cfg, mix, 2**31 + 9, 3)
run = {"config": cfg, "traffic": mix, "chips": 1,
       "device_kind": "TPU v5 lite", "frames": 4096, "updates": 8,
       "trace": {"window_s": 2.0, "devices": [
           {"op_counts": {"ring_gather": 8}, "ops_s": {"ring_gather": 1e-3}}]}}
print(json.dumps({
    "networks": pipeline.networks(cfg).__file__,
    "leaves": sorted(ref["change"]), "change": ref["change"],
    "losses": ref["losses"], "readings": compare.readings(ref, ref),
    "flops": counts.flops_per_env_frame(cfg, mix),
    "rows": counts.rows_per_sample(cfg, mix),
    "megastep_mfu": cat.reader("megastep_mfu")(run),
    "ring_gather_roofline": cat.reader("ring_gather_roofline")(run),
    "cfg": cfg, "mix": mix, "payload": counts.payload_bytes(cfg)}))
"""


def test_dummy_addition_is_files_only(tmp_path):
    """Copy the benchmark, add a configuration that brings its own env
    and networks module (a rollout state, a 2-frame window batch, an
    encoder trained by the critic, its own counts), a mix, a limits
    file and a metric reader plus their entries, and see the harness
    list and find them, the reference follow the new cell, and the
    count-reading metrics read its counts, with no edit to any file
    that was there."""
    here = Catalog()
    shutil.copy(os.path.join(here.root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(here.bench, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "sac_pendulum.json").read_text())
    (b / "configs" / "dummy_cfg.json").write_text(json.dumps(
        dict(cfg, env="dummy_env", networks="dummy_window")))
    (b / "reference" / "env_dummy_env.py").write_text(
        "from reference.env_pendulum import observe, reset, step  # noqa\n")
    shutil.copy(os.path.join(FIXTURES, "networks_dummy_window.py"),
                b / "reference")
    mix = json.loads((b / "traffic" / "uniform_utd24.json").read_text())
    (b / "traffic" / "dummy_mix.json").write_text(json.dumps(
        dict(mix, updates_per_round=1)))
    (b / "limits" / "dummy_cfg.dummy_mix.json").write_text(json.dumps(
        {"loss_gap": 1, "grad_gap": 1, "change_gap": 1}))
    (b / "metrics" / "dummy_metric.py").write_text(
        "def read(run):\n    return run['frames'] * 2.0\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "dummy_cfg", "source": "x",
                            "file": "bench/configs/dummy_cfg.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "dummy_cfg.dummy_mix",
                              "config": "dummy_cfg", "traffic": "dummy_mix",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "dummy_metric", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "test", "moves": "env_frames_per_s",
                              "workloads": ["dummy_cfg.dummy_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cat = Catalog(str(tmp_path))
    assert "dummy_cfg" in cat.names("configs")
    assert "dummy_mix" in cat.names("traffic")
    assert "dummy_metric" in cat.names("metrics")
    cell, cfg2, mix2, limits = cat.workload("dummy_cfg.dummy_mix")
    assert cfg2["env"] == "dummy_env" and mix2["updates_per_round"] == 1
    assert "dummy_metric" in [m["name"]
                              for m in cat.metrics("per_layer", cell)]
    assert cat.reader("dummy_metric")({"frames": 3}) == 6.0
    # the dummy metric is reported in its cell only
    other = cat.workload("sac_hopper.per_n3_utd2")[0]
    assert "dummy_metric" not in [m["name"]
                                  for m in cat.metrics("per_layer", other)]

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1")
    p = subprocess.run([sys.executable, "-c", FOLLOW_DUMMY], cwd=tmp_path,
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["networks"] == str(b / "reference" / "networks_dummy_window.py")
    # every trained group is followed and moves, the encoder among them
    assert {k.split(".")[0] for k in got["leaves"]} == {
        "actor", "q", "encoder", "log_alpha"}
    assert all(got["change"][k] > 0 for k in got["leaves"]
               if k.startswith("encoder."))
    assert len(got["losses"]) == 3 and all(
        math.isfinite(x) for d in got["losses"] for x in d)
    assert got["readings"]["change_gap"] == 0.0
    # the counts are the dummy module's own, never the MLP's
    window = _module(b / "reference" / "networks_dummy_window.py")
    cfg3, mix3 = got["cfg"], got["mix"]
    flops = window.flops_per_env_frame(cfg3, mix3)
    assert got["flops"] == flops and got["rows"] == 2
    from reference import networks_mlp
    assert flops != networks_mlp.flops_per_env_frame(cfg3, mix3)
    rate, peak, hbm = 4096 / 2.0, 197e12, 819e9
    assert got["megastep_mfu"] == 100.0 * flops * rate / peak
    assert got["ring_gather_roofline"] == \
        100.0 * 8 * 16 * 2 * got["payload"] / hbm / 1e-3
    after = {p: p.read_bytes() for p in before}
    assert after == before


def _module(path):
    import importlib.util
    spec = importlib.util.spec_from_file_location("dummy_window", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
