"""Cells are found from their files alone, and a run off the TPU prints
nothing."""
import json
import subprocess
import sys

import pytest

import onchip_testkit
import harness


def test_a_new_cell_is_found_from_its_files(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "mixes").mkdir()
    (tmp_path / "limits").mkdir()
    cfg = dict(onchip_testkit.TINY, weight_mode="shared")
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (tmp_path / "mixes" / "trickle.json").write_text(json.dumps(
        {"kind": "closed_loop", "request_samples": 4}))
    (tmp_path / "limits" / "tiny.trickle.json").write_text(json.dumps(
        {"control": "high", "limits": {"out_gap": {"limit": 1e-4}}}))
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": str(tmp_path / "configs" / "tiny.json"),
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.trickle", "config": "tiny",
                               "traffic": "trickle", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0].setdefault("workloads", []).append("tiny.trickle")
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "device", "moves": "x",
                               "workloads": ["tiny.trickle"]})
    spec = harness.load_spec("tiny.trickle", bench=bench, base=tmp_path)
    assert spec.config == cfg and spec.mix["request_samples"] == 4
    assert spec.limits == {"out_gap": {"limit": 1e-4}}
    assert bench["end_to_end"][0]["name"] in [m["name"]
                                              for m in spec.end_to_end]
    assert "setup_s" in [m["name"] for m in spec.end_to_end]
    assert [m["name"] for m in spec.per_layer] == ["new_metric"]
    with pytest.raises(KeyError):
        harness.load_spec("no.such.cell", bench=bench, base=tmp_path)


def test_run_without_a_tpu_exits_nonzero_and_prints_nothing(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path)}
    out = subprocess.run(
        [sys.executable, str(harness.HERE / "run.py"), "--workload",
         "fno2d.serve.bulk", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, env=env, cwd=harness.ROOT,
        timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
