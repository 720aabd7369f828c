"""bench.py never answers for a chip it did not run on, and the compile
cache is placed from outside.

- the peak table raises on a ``device_kind`` it does not know;
- with no chip, ``bench.main()`` exits non-zero and prints no result, and
  on the chip path its parent process touches no backend;
- ``configure_compile_cache`` sets nothing where the machine names a
  cache, and a fixed in-checkout path otherwise.
"""

import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import bench  # noqa: E402
from paddle_tpu.profiler import accounting  # noqa: E402
from paddle_tpu.utils import compile_cache  # noqa: E402


def test_peak_lookup_raises_on_unknown_device_kind():
    assert bench.peak_bf16_flops("TPU v5 lite") == 197e12
    # jax names v5p "TPU v5": an exact key, not a v5 catch-all
    assert accounting.peak_bf16_flops("TPU v5") == 459e12
    for kind in ("TPU v5 mega", "TPU v9", "cpu", ""):
        with pytest.raises(ValueError, match="no bf16 peak known"):
            bench.peak_bf16_flops(kind)


def test_accounting_peak_is_none_on_cpu_only(monkeypatch):
    monkeypatch.delenv("ACCOUNTING_PEAK_FLOPS", raising=False)
    assert accounting.detect_peak_flops() is None  # the CPU test backend

    class Unknown:
        platform = "tpu"
        device_kind = "TPU v5 mega"

    monkeypatch.setattr(jax, "devices", lambda *a: [Unknown()])
    with pytest.raises(ValueError, match="no bf16 peak known"):
        accounting.detect_peak_flops()


def test_bench_without_chip_exits_nonzero(monkeypatch, capsys):
    """No chip answers the probe and JAX_PLATFORMS=cpu was not asked for:
    no smoke line, no cached headline, exit code 1."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(bench, "_probe_backend_subprocess",
                        lambda *a, **k: "cpu")
    ran = []
    monkeypatch.setattr(bench, "_run_rung_subprocess",
                        lambda name, **k: ran.append(name) or {})
    assert bench.main() == 1
    out = capsys.readouterr()
    assert out.out == "" and "no chip" in out.err
    assert not ran


def test_bench_failed_headline_exits_nonzero(monkeypatch, capsys):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(bench, "_probe_backend_subprocess",
                        lambda *a, **k: "tpu")
    ran = []

    def rung(name, **k):
        ran.append(name)
        return {"skipped": "RESOURCE_EXHAUSTED"}

    monkeypatch.setattr(bench, "_run_rung_subprocess", rung)
    assert bench.main() == 1
    out = capsys.readouterr()
    assert out.out == "" and "headline rung failed" in out.err
    assert ran == ["head"]  # no ladder behind a dead headline


def test_bench_parent_touches_no_backend(monkeypatch, capsys):
    """A chip belongs to one process: on the chip path the parent only
    spawns rung children, so a whole main() makes no backend access."""
    import json

    from jax._src import xla_bridge

    def touched(*a, **k):
        raise AssertionError("bench's parent process touched a backend")

    head = {"tokens_per_s": 1.0, "mfu": 0.5, "device": "TPU v5 lite",
            "step_time_ms": 1.0, "loss": 1.0, "batch": 8, "seq": 1024,
            "params": 1}
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(bench, "_probe_backend_subprocess",
                        lambda *a, **k: "tpu")
    monkeypatch.setattr(bench, "_run_rung_subprocess",
                        lambda name, **k: dict(head))
    monkeypatch.setattr(xla_bridge, "get_backend", touched)
    monkeypatch.setattr(xla_bridge, "backends", touched)
    assert bench.main() == 0
    line = json.loads(capsys.readouterr().out)
    assert line["metric"] == "gpt2_345m_pretrain_tokens_per_sec_per_chip"
    assert set(line["ladder"]) == {n for n, _ in bench._tpu_rung_specs()
                                   if n != "head"}


# the named scopes of a program live in its operations' metadata, which
# jax leaves out of the cache's key unless told otherwise
_METADATA_IN_KEY = ("jax_compilation_cache_include_metadata_in_key", True)


def test_compile_cache_env_set_writes_no_directory(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    wrote = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: wrote.append(a))
    assert compile_cache.configure_compile_cache() == "/somewhere/else"
    assert wrote == [_METADATA_IN_KEY]


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    wrote = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: wrote.append(a))
    want = os.path.join(REPO, ".jax_compile_cache")
    assert compile_cache.configure_compile_cache() == want
    assert compile_cache.configure_compile_cache() == want  # no pid, no time
    assert wrote == [_METADATA_IN_KEY,
                     ("jax_compilation_cache_dir", want)] * 2
