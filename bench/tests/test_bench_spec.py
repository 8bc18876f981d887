"""The benchmark's files load by name, and its arithmetic holds by hand."""
import json

import pytest

import bench_tiny  # noqa: F401  (puts bench/ and src/ on the path)
from benchlib import device, flops, spec
from benchlib.traffic import batch_at, make_tokens

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load_by_name(name):
    cell = spec.load_cell(name)
    assert cell.config["name"] == cell.config_name
    spec.reference_module(cell.config)
    assert (spec.ROOT / "bench" / "limits" / f"{name}.json").is_file()
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.metric_reader(m.name))


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda e: e["name"])
def test_per_layer_metric_moves_a_metric_its_cells_report(entry):
    for name in entry.get("workloads", CELLS):
        cell = spec.load_cell(name)
        assert entry["moves"] in {m.name for m in cell.end_to_end}, name


def test_every_cell_reports_setup_and_another_metric():
    for name in CELLS:
        cell = spec.load_cell(name)
        names = {m.name for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_config_reduced_keys_match_benchmark():
    for c in BENCH["configs"]:
        config = json.loads((spec.ROOT / c["file"]).read_text())
        assert sorted(config["reduced"]) == sorted(c["reduced"])


def test_new_cell_config_and_metric_are_files_and_entries_only(tmp_path):
    """A later cell, configuration and per-layer metric come as new files and
    new BENCHMARK.json entries; every file already here is left as it is."""
    import shutil

    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    config = json.loads((spec.ROOT / BENCH["configs"][0]["file"]).read_text())
    config.update(name="new-config", num_hidden_layers=2)
    (tmp_path / "bench/configs/new-config.json").write_text(json.dumps(config))
    traffic = spec.load_json(spec.BENCH_DIR / "traffic" / "train.json")
    (tmp_path / "bench/traffic/new-mix.json").write_text(
        json.dumps(dict(traffic, batch=2)))
    (tmp_path / "bench/metrics/new_share.py").write_text(
        "def read(run):\n    return None\n")
    bench["configs"].append(dict(BENCH["configs"][0], name="new-config",
                                 file="bench/configs/new-config.json"))
    bench["workloads"].append({"name": "new-config.new-mix",
                               "config": "new-config", "traffic": "new-mix",
                               "chips": 1, "why": "a later cell"})
    bench["per_layer"].append({"name": "new_share", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "setup_s",
                               "workloads": ["new-config.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("new-config.new-mix", root=tmp_path)
    assert cell.config["num_hidden_layers"] == 2
    assert cell.traffic["batch"] == 2
    assert "new_share" in {m.name for m in cell.per_layer}
    assert spec.metric_reader("new_share", root=tmp_path)(None) is None
    spec.reference_module(cell.config, root=tmp_path)
    assert all(p.read_bytes() == b for p, b in before.items())


def test_unknown_workload_is_refused():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")


def test_peaks_table_refuses_unknown_device_kind():
    assert device.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        device.peaks("cpu")


def test_train_flops_against_a_hand_count():
    cfg = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
           "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
           "vocab_size": 10}
    # per layer: q 8*4*2=64, k and v 8*2*2=32 each, o 4*2*8=64,
    # gate, up and down 8*16=128 each: 576; two layers 1152; head 10*8=80
    assert flops.matmul_params(cfg) == 1232
    # 6 * 1232 + 6 * L(2) * T(5) * H(4) * hd(2) = 7392 + 480
    assert flops.train_flops_per_token(cfg, 5) == 7872
    assert flops.train_step_flops(cfg, 3, 5) == 7872 * 15


def test_tokens_are_seeded_and_batches_whole():
    traffic = {"batch": 2, "seq_len": 16,
               "data": {"motifs": 4, "motif_len": 4, "noise": 0.1,
                        "batches": 3}}
    big_seed = 2**31 + 99
    a = make_tokens(traffic, 50, big_seed)
    assert (a == make_tokens(traffic, 50, big_seed)).all()
    assert not (a == make_tokens(traffic, 50, big_seed + 1)).all()
    b0, b3 = batch_at(a, traffic, 0), batch_at(a, traffic, 3)
    assert b0["tokens"].shape == (2, 16)
    assert (b0["tokens"][:, 1:] == b0["labels"][:, :-1]).all()
    assert (b0["tokens"] == b3["tokens"]).all()      # wraps after 3 batches
