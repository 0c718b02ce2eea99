"""BENCHMARK.json and the files it names: every one parses, is found by
name, keeps to the allowed characters and states the published widths;
a new cell needs only new files."""
import json
import math

import pytest

from bench import spec, traffic

BENCH = spec.load_benchmark()


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (spec.ROOT / p).is_dir()


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_is_found_by_name(w):
    cell = spec.cell(w["name"])
    assert cell.chips in (1, 4)
    assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
    assert cell.per_layer
    for name in list(cell.end_to_end) + list(cell.per_layer):
        assert callable(spec.metric_reader(name))
    assert cell.traffic["loop"] in ("open", "backlog")
    assert math.isclose(sum(cell.traffic["shares"]), 1.0)
    spec.form(cell.config["form"])


def test_names_and_units_use_allowed_characters():
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    assert all(spec.NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert spec.UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


CONFIG_FILES = sorted((spec.ROOT / "bench" / "configs").glob("*.json"))


def test_listed_configs_match_their_files():
    for c in BENCH["configs"]:
        config = json.loads((spec.ROOT / c["file"]).read_text())
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_config_states_source_cut_and_published_widths(path):
    config = json.loads(path.read_text())
    assert config["source"].startswith("https://")
    assert config["assumed"] and config["deployment"]
    for k in config["reduced"]:
        assert k in config["published"], k
    m, pub = config["model"], config["published"]
    heads = pub["num_attention_heads"]
    assert m["n_heads"] == m["n_kv_heads"] == heads
    assert m["head_dim"] == pub["attention_head_dim"]
    assert m["d_model"] == heads * pub["attention_head_dim"]
    assert m["d_ff"] == 4 * m["d_model"]
    assert m["dtype"] == pub["torch_dtype"]


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A cell with a config of a new architecture, a backlog mix and a
    metric of its own: each a new file, found by its name."""
    for d in ("configs", "traffic", "metrics", "forms"):
        (tmp_path / "bench" / d).mkdir(parents=True)
    (tmp_path / "bench" / "configs" / "new.json").write_text(
        json.dumps({"form": "new_form", "model": {"n_layers": 1}}))
    (tmp_path / "bench" / "forms" / "new_form.py").write_text(
        "PROGRAM_KEYS = {'act': 'swiglu'}\n"
        "def forward_flops(config, rows, latent):\n"
        "    return 7.0 * rows * latent\n")
    (tmp_path / "bench" / "traffic" / "video.json").write_text(json.dumps(
        {"loop": "backlog", "requests": 6, "lengths": [17552],
         "shares": [1.0], "drain_s": 300, "trace_s": 12}))
    (tmp_path / "bench" / "traffic" / "burst.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 2.0, "lengths": [1024],
         "shares": [1.0]}))
    (tmp_path / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench = dict(BENCH)
    bench["configs"] = BENCH["configs"] + [
        {"name": "new", "file": "bench/configs/new.json"}]
    bench["workloads"] = BENCH["workloads"] + [
        {"name": "new_cell", "config": "new", "traffic": "burst",
         "chips": 1},
        {"name": "new_video", "config": "new", "traffic": "video",
         "chips": 4}]
    bench["per_layer"] = BENCH["per_layer"] + [
        {"name": "new_metric", "unit": "%", "workloads": ["new_cell"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.cell("new_cell", root=tmp_path)
    assert cell.config == {"form": "new_form", "model": {"n_layers": 1}}
    assert cell.traffic["rate_per_s"] == 2.0
    assert "new_metric" in cell.per_layer
    assert spec.metric_reader("new_metric", root=tmp_path)(None) == 42.0
    form = spec.form(cell.config["form"], root=tmp_path)
    assert form.PROGRAM_KEYS == {"act": "swiglu"}
    assert form.forward_flops(cell.config, 2, 3) == 42.0
    video = spec.cell("new_video", root=tmp_path)
    assert len(traffic.schedule(video.traffic, 1, 50.0)) == 6
    with pytest.raises(KeyError):
        spec.cell("no_such_cell", root=tmp_path)


def test_peaks_know_the_v5e_and_refuse_unknown_kinds():
    assert spec.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peak("TPU v99")


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3])
def test_open_traffic_is_deterministic_and_keeps_rate_and_shares(seed):
    tr = {"loop": "open", "rate_per_s": 1.5, "lengths": [1024, 2304, 4096],
          "shares": [0.4, 0.3, 0.3]}
    a = traffic.open_schedule(tr, seed, 50.0)
    assert a == traffic.open_schedule(tr, seed, 50.0)
    assert len(a) == 75
    assert all(0 < x.due < 50.0 for x in a)
    assert [x.due for x in a] == sorted(x.due for x in a)
    lens = [x.length for x in a]
    assert (lens.count(1024), lens.count(2304), lens.count(4096)) == (
        30, 22, 23) or (lens.count(1024), lens.count(2304),
                        lens.count(4096)) == (30, 23, 22)
    other = traffic.open_schedule(tr, seed + 1, 50.0)
    assert sorted(lens) == sorted(x.length for x in other)
    # the same gaps in another order
    def gaps(s):
        return [s[0].due] + [y.due - x.due for x, y in zip(s, s[1:])]

    assert sorted(gaps(a)) == pytest.approx(sorted(gaps(other)))
    assert gaps(a) != pytest.approx(gaps(other))
    assert sum(gaps(a)) / len(a) == pytest.approx(1 / 1.5, rel=0.02)


def test_apportion_sums_and_follows_shares():
    assert traffic.apportion([0.4, 0.3, 0.3], 10) == [4, 3, 3]
    assert sum(traffic.apportion([0.4, 0.3, 0.3], 77)) == 77
    assert traffic.lengths({"lengths": [5, 6], "shares": [0.5, 0.5]}, 4,
                           3).count(5) == 2


def test_a_mix_with_a_schedule_seed_replays_one_trace():
    tr = {"loop": "open", "rate_per_s": 2.0, "lengths": [1024, 4096],
          "shares": [0.5, 0.5], "schedule_seed": 9}
    assert traffic.open_schedule(tr, 1, 50.0) == traffic.open_schedule(
        tr, 2**40 + 1, 50.0)
    assert "schedule_seed" in spec.cell("flux_img_mix").traffic


def test_every_traffic_and_metric_file_loads():
    for path in (spec.ROOT / "bench" / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        assert traffic.schedule(mix, 1, 10.0)
        assert len(mix["lengths"]) == len(mix["shares"])
    for path in (spec.ROOT / "bench" / "metrics").glob("*.py"):
        assert spec.NAME.match(path.stem)
        assert callable(spec.metric_reader(path.stem))


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**40 + 3])
def test_backlog_is_a_fixed_set_all_due_at_the_open(seed):
    tr = {"loop": "backlog", "requests": 7, "lengths": [4096, 17552],
          "shares": [0.3, 0.7], "drain_s": 300}
    a = traffic.schedule(tr, seed, 50.0)
    assert len(a) == 7 and all(x.due == 0.0 for x in a)
    lens = [x.length for x in a]
    assert (lens.count(4096), lens.count(17552)) == (2, 5)
    assert a == traffic.schedule(tr, seed, 5.0)  # the window does not size it
    orders = {tuple(x.length for x in traffic.schedule(tr, s, 50.0))
              for s in range(seed, seed + 8)}
    assert len(orders) > 1  # the order is drawn from the run's seed
    fixed = dict(tr, schedule_seed=9)
    assert traffic.schedule(fixed, seed, 50.0) == traffic.schedule(
        fixed, seed + 1, 50.0)


def test_an_open_mix_keeps_its_schedule_and_an_unknown_loop_fails():
    tr = spec.cell("flux_img_mix").traffic
    assert traffic.schedule(tr, 3, 50.0) == traffic.open_schedule(
        tr, 3, 50.0)
    with pytest.raises(ValueError, match="closed"):
        traffic.schedule(dict(tr, loop="closed"), 3, 50.0)
