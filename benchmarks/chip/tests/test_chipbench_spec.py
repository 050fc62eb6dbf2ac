"""The harness finds what BENCHMARK.json names, by name, and refuses to
run without a chip."""

import json
import os
import re
import subprocess
import sys

import chipbench_testlib as TL
import pytest

from harness import spec


def test_every_named_file_is_found():
    bench = json.loads((TL.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.chips == w["chips"]
        for m in cell.per_layer:
            assert callable(cell.readers[m["name"]].read)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    for c in bench["configs"]:
        assert (TL.ROOT / c["file"]).is_file()
        ref = spec.load_config(c["name"])["reference"]
        spec.load_reference(ref)
        fam = spec.load_family(ref)
        for fn in ("check", "program_params", "decode_step_flops",
                   "paged_attention_cost", "attention_layers"):
            assert callable(getattr(fam, fn)), (ref, fn)


def test_reduced_names_every_changed_key():
    """BENCHMARK.json's ``reduced`` lists exactly the model keys that each
    configuration file says it changed from its source."""
    bench = json.loads((TL.ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        changed = spec.load_config(c["name"]).get("changed", {})
        assert set(c["reduced"]) == {k for k in changed
                                     if k.startswith("models.")}, c["name"]


def test_a_cell_added_as_files_is_found(tmp_path):
    """A new configuration, architecture family, traffic mix and metric
    are found from their files and a BENCHMARK.json entry, with no code
    changed."""
    bdir = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "families"):
        (bdir / sub).mkdir(parents=True)
    (bdir / "configs" / "newcfg.json").write_text(json.dumps(
        {"name": "newcfg", "reference": "newarch", "router": {}}))
    (bdir / "families" / "newarch.py").write_text("NAME = 'newarch'\n")
    (bdir / "traffic" / "newmix.json").write_text(json.dumps(
        {"kind": "chat", "rate_rps": 1.0}))
    (bdir / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "newcfg.newmix", "config": "newcfg",
                       "traffic": "newmix", "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "setup_s"}],
        "per_layer": [{"name": "new.metric", "workloads": ["newcfg.newmix"]},
                      {"name": "other", "workloads": ["elsewhere"]}]}))
    cell = spec.load_cell("newcfg.newmix", root=tmp_path, bench_dir=bdir)
    assert cell.traffic["rate_rps"] == 1.0 and cell.traffic["name"] == "newmix"
    assert [m["name"] for m in cell.per_layer] == ["new.metric"]
    assert cell.readers["new.metric"].read(None) == 42.0
    assert cell.family.NAME == "newarch"
    with pytest.raises(spec.SpecError):
        spec.load_cell("missing", root=tmp_path, bench_dir=bdir)


def test_a_configuration_without_its_family_is_refused(tmp_path):
    """A configuration whose ``reference`` names no ``families/`` file is
    refused before anything runs, naming the file it looked for."""
    bdir = tmp_path / "bench"
    for sub in ("configs", "traffic"):
        (bdir / sub).mkdir(parents=True)
    (bdir / "configs" / "newcfg.json").write_text(json.dumps(
        {"name": "newcfg", "reference": "nosuch", "router": {}}))
    (bdir / "traffic" / "newmix.json").write_text(json.dumps({"kind": "chat"}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "newcfg.newmix", "config": "newcfg",
                       "traffic": "newmix", "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "setup_s"}], "per_layer": []}))
    with pytest.raises(spec.SpecError,
                       match=re.escape(str(bdir / "families" / "nosuch.py"))):
        spec.load_cell("newcfg.newmix", root=tmp_path, bench_dir=bdir)


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(TL.BENCH / "run.py"),
                        "--workload", "mom.chat_poisson", "--seed", "3",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, timeout=120, env=env,
                       cwd=str(TL.ROOT))
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
