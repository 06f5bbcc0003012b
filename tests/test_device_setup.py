"""Process set-up shared by the launchers and chip_smoke.py: the
persistent compile cache placement, the kernel interpret resolution,
and the in-process train entry point."""
import math

import jax
import pytest

from repro.kernels import resolve_interpret
from repro.launch import device


def test_compile_cache_env_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert device.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = device.enable_compile_cache()
        assert path == str(device.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert device.CACHE_DIR.name == ".jax_cache"
        assert (device.CACHE_DIR.parent / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_resolve_interpret_by_platform(monkeypatch):
    from jax.experimental.pallas import tpu as pltpu
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert isinstance(resolve_interpret(), pltpu.InterpretParams)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        resolve_interpret()


def test_train_main_in_process(monkeypatch, tmp_path, capsys):
    """The launcher runs in-process (as chip_smoke.py drives it) and
    reports blocked step times apart from compile times."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    from repro.launch import train
    run = train.main(["--arch", "moe-gpt2", "--reduced", "--steps", "2",
                      "--seq-len", "64", "--global-batch", "2",
                      "--mesh", "none"])
    assert run["device"]["platform"] == "cpu"
    assert len(run["losses"]) == len(run["step_s"]) == 2
    assert all(math.isfinite(x) for x in run["losses"])
    assert len(run["compile_s"]) >= 1
    assert run["expert_shard"] == [2, 4, 256, 512]
    out = capsys.readouterr().out
    assert "devices: platform=cpu" in out and "compile bucket=0" in out


def test_train_trace_writes_the_profiler_trace_beside_its_json(
        monkeypatch, tmp_path):
    """--trace names the loop's spans as the benchmark does (upload, step,
    metrics, bucket) and writes the JAX profiler's trace beside the
    Chrome JSON, with those spans on the profiler's host plane."""
    import glob
    import json
    from jax.profiler import ProfileData
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    from repro.launch import train
    out = tmp_path / "trace.json"
    try:
        train.main(["--arch", "moe-gpt2", "--reduced", "--steps", "2",
                    "--seq-len", "64", "--global-batch", "2",
                    "--mesh", "none", "--trace-out", str(out)])
        # the cache may not hand the step another program's op_names
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
    names = {e["name"] for e in json.loads(out.read_text())["traceEvents"]}
    loop = {"upload", "step", "metrics", "bucket"}
    assert loop <= names, names
    (xplane,) = glob.glob(str(tmp_path / "trace.profile" / "**" /
                              "*.xplane.pb"), recursive=True)
    host = {ev.name for plane in ProfileData.from_file(xplane).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert loop <= host, loop - host
