"""``chip_smoke.py`` and the plumbing it rests on, on the CPU: the script
refuses to run without a TPU, its phases pass at ``tiny()`` size with
the kernels in interpret mode, and the compile cache lands where it
should."""

import dataclasses
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from raytpu.models.gpt2 import GPT2Config
from raytpu.util import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dataclasses.replace(GPT2Config.tiny(), dtype=jnp.float32,
                           attn_impl="interpret", paged_attn="interpret",
                           remat=False)
OPTIONS = {"page_size": 8, "max_num_seqs": 4, "prefill_chunk": 16}


def _run(code_or_script, env_extra, *, script=False):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV}
    env.update(env_extra, PYTHONPATH=ROOT)
    cmd = [sys.executable] + ([code_or_script] if script
                              else ["-c", code_or_script])
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=300, cwd=ROOT)


class TestRefusesWithoutATpu:
    def test_cpu_run_fails_and_prints_no_result(self):
        r = _run(os.path.join(ROOT, "chip_smoke.py"),
                 {"JAX_PLATFORMS": "cpu"}, script=True)
        assert r.returncode != 0
        assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr
        assert '"ok"' not in r.stdout

    def test_bench_refuses_too(self):
        r = _run(os.path.join(ROOT, "bench.py"), {"JAX_PLATFORMS": "cpu"},
                 script=True)
        assert r.returncode != 0
        assert "TPU" in r.stderr and r.stdout.strip() == ""


class TestCompileCache:
    PROBE = ("import json, jax\n"
             "from raytpu.util import compile_cache\n"
             "print(json.dumps([compile_cache.enable(),\n"
             "                  jax.config.jax_compilation_cache_dir,\n"
             "                  compile_cache.spawn_env()]))\n")

    def _probe(self, env):
        # No platform named, as on a machine with a chip; the probe
        # reads configuration only and starts no backend.
        r = _run(self.PROBE, dict({"JAX_PLATFORMS": ""}, **env))
        assert r.returncode == 0, r.stderr
        return json.loads(r.stdout.strip().splitlines()[-1])

    def test_unset_is_the_checkout_in_every_process(self):
        want = os.path.join(ROOT, ".jax_cache")
        first, second = self._probe({}), self._probe({})
        assert first == second == [want, want, {compile_cache.ENV: want}]

    def test_a_process_held_to_the_cpu_gets_none(self):
        assert self._probe({"JAX_PLATFORMS": "cpu"}) == [None, None, {}]

    def test_set_from_outside_is_left_to_jax(self, tmp_path, monkeypatch):
        # JAX reads the variable itself; the helper sets no directory.
        placed = str(tmp_path / "cache")
        assert self._probe({compile_cache.ENV: placed}) == [
            placed, placed, {compile_cache.ENV: placed}]
        monkeypatch.setenv(compile_cache.ENV, placed)
        monkeypatch.setattr(
            "jax.config.update",
            lambda *a: pytest.fail(f"set in code: {a}"))
        assert compile_cache.enable() == placed


class TestPhasesAtTinySize:
    @pytest.mark.parametrize("chips", [1, 2])
    def test_logits_phase(self, chips):
        facts = chip_smoke.logits_phase(
            TINY, page_size=OPTIONS["page_size"],
            chunk=OPTIONS["prefill_chunk"], chips=chips, tol=1e-4)
        assert set(facts["rel_err"]) == {"prefill", "chunk", "decode",
                                         "decode_long"}
        if chips > 1:
            assert facts["spread"]["pool_shard_heads"] == [1, 1]

    def test_logits_phase_catches_a_wrong_kernel(self, monkeypatch):
        pa = sys.modules["raytpu.ops.paged_attention"]
        kernel = pa._paged_pallas
        monkeypatch.setattr(
            pa, "_paged_pallas", lambda *a, **kw: kernel(*a, **kw) * 1.5)
        with pytest.raises(RuntimeError, match="logits differ"):
            chip_smoke.logits_phase(
                TINY, page_size=OPTIONS["page_size"],
                chunk=OPTIONS["prefill_chunk"], tol=1e-4)

    def test_window_phase(self):
        from raytpu.models.mixtral import MellumConfig

        tiny = dataclasses.replace(
            MellumConfig.tiny(), dtype=TINY.dtype, attn_impl="interpret",
            paged_attn="interpret", remat=False)
        facts = chip_smoke.window_phase(tiny, page_size=4, chunk=16,
                                        tol=1e-4)
        assert facts["prompt_tokens"] == [29, 12]
        assert set(facts["rel_err"]) == {"prompt_29", "prompt_12"}
        assert facts["window_pages_released"] >= 6
        assert facts["live_pages_window_max"] <= 2 * 3

    def test_window_phase_catches_an_ignored_window(self, monkeypatch):
        from raytpu.models.mixtral import MellumConfig

        pa = sys.modules["raytpu.ops.paged_attention"]
        kernel = pa._paged_pallas
        monkeypatch.setattr(
            pa, "_paged_pallas",
            lambda *a, window=None, **kw: kernel(*a, **kw))
        tiny = dataclasses.replace(
            MellumConfig.tiny(), dtype=TINY.dtype, attn_impl="interpret",
            paged_attn="interpret", remat=False)
        with pytest.raises(RuntimeError, match="window-layer logits"):
            chip_smoke.window_phase(tiny, page_size=4, chunk=16, tol=1e-4)

    def test_serve_phase(self, raytpu_local):
        facts = chip_smoke.serve_phase(TINY, OPTIONS, new_tokens=6,
                                       expect_impl="interpret")
        assert facts["streams"] == {name: 6 for name in (
            "short-a", "short-b", "long", "shared-1", "shared-2")}
        assert facts["max_decode_batch"] > 1

    @pytest.mark.parametrize("chips", [1, 2])
    def test_train_phase(self, raytpu_local, chips):
        facts = chip_smoke.train_phase(TINY, batch=2, steps=2, chips=chips,
                                       expect_kernels=False)
        assert len(facts["losses"]) == 2
        assert len(facts["param_bytes_per_device"]) == chips

    def test_train_phase_raises_on_a_failed_gang(self, raytpu_local):
        broken = dataclasses.replace(TINY, attn_impl="no-such-impl")
        with pytest.raises(RuntimeError, match="training failed"):
            chip_smoke.train_phase(broken, batch=2, steps=1,
                                   expect_kernels=False)


@pytest.mark.slow
def test_replicas_phase_on_a_cpu_cluster():
    """One replica process per (fake) chip behind a real head and node,
    driven by a process that never imports JAX."""
    code = (
        "import json, chip_smoke\n"
        "facts = chip_smoke.replicas_phase(\n"
        "    2, %r, expect_platform='cpu', model_config=dict(\n"
        "        vocab_size=512, block_size=128, n_layer=2, n_head=2,\n"
        "        n_embd=128))\n"
        "print(json.dumps(facts))\n" % (OPTIONS,))
    r = _run(code, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    replicas = json.loads(r.stdout.strip().splitlines()[-1])["replicas"]
    assert sorted(x["chips"] for x in replicas.values()) == ["0", "1"]
