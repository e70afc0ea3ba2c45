"""chip_smoke.py's contract off the chip, and the rules it rests on.

The smoke itself only proves something on a TPU (the chip tool runs it
there). What CAN be pinned on CPU: the plain invocation refuses to run
anywhere else, the rehearsal is labelled as one, the compile cache is
placed from outside the program, and nothing between a Pallas kernel
and its caller turns a compile failure into a quiet switch of path.
"""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.optimize import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, **env_over):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_over)
    env.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices: not here
    return subprocess.run([sys.executable, SMOKE, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=900)


class TestInvocation:
    def test_plain_invocation_off_tpu_exits_nonzero_naming_platform(self):
        out = _run()
        assert out.returncode != 0
        assert "platform is 'cpu'" in out.stderr
        assert "JAX_PLATFORMS='cpu'" in out.stderr
        # the device report comes first; no phase ran, no result printed
        assert "platform=cpu" in out.stdout
        assert "[train]" not in out.stdout
        assert '"ok"' not in out.stdout

    def test_rehearsal_exits_zero_and_is_labelled(self, tmp_path):
        out = _run("--rehearse",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
        assert '"rehearsal": true' in out.stdout
        for phase in ("train", "serve", "generate", "kernels",
                      "attention-layer"):
            assert f"[{phase}] ok" in out.stdout
        assert f"compile cache dir={tmp_path / 'cache'}" in out.stdout
        last = json.loads(out.stdout.strip().splitlines()[-1])
        assert last == {"ok": True, "rehearsal": True,
                        "device": {"platform": "cpu", "kind": "cpu",
                                   "count": 1}}


class TestCacheRule:
    def test_env_places_the_cache(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert compile_cache.resolve_cache_dir() == "/some/dir"

    def test_unset_is_the_fixed_in_checkout_directory(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        d = compile_cache.resolve_cache_dir()
        assert d == os.path.join(REPO, ".jax_cache")
        ignored = open(os.path.join(REPO, ".gitignore")).read().split()
        assert ".jax_cache/" in ignored

    def test_no_other_code_places_the_cache(self):
        """Only compile_cache.py names the cache directory, and it builds
        the path from nothing that changes between runs."""
        sources = [SMOKE]
        for root, _, files in os.walk(os.path.join(REPO,
                                                   "deeplearning4j_tpu")):
            sources += [os.path.join(root, f) for f in files
                        if f.endswith(".py")]
        owner = os.path.join(REPO, "deeplearning4j_tpu", "optimize",
                             "compile_cache.py")
        def code_of(path):  # comments may name it; code may not
            return "\n".join(ln.split("#")[0]
                             for ln in open(path).read().splitlines())
        for path in sources:
            if path != owner:
                assert not re.search(
                    r"jax_compilation_cache_dir|JAX_COMPILATION_CACHE_DIR",
                    code_of(path)), \
                    f"{path} places the compile cache itself"
        code = code_of(owner)
        for moving in ("tempfile", "getpid", "time.", "uuid", "/tmp",
                       "expanduser"):
            assert moving not in code, \
                f"compile_cache.py builds a path from {moving!r}"


class TestNoSilentKernelFallback:
    """Off-TPU the dispatch says no without compiling; where it says yes
    (a mocked "tpu" backend on this CPU host, so the real compile cannot
    succeed) the compiler's refusal reaches the caller."""

    def _qkv(self, t=256, h=2, d=32):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        return tuple(jax.random.normal(k, (1, t, h, d), jnp.float32)
                     for k in ks)

    def test_off_tpu_answer_is_no_without_compiling(self, monkeypatch):
        def boom(*a, **kw):
            raise AssertionError("tried to compile a kernel off-TPU")
        monkeypatch.setattr(fa, "_fwd_call", boom)
        assert fa.flash_attention_available() is False
        assert att.select_attention_impl(4096, 128) == "blockwise"
        q, k, v = self._qkv()
        arena = k.reshape(1, 16, 16, 64)            # 16 blocks of 16
        out = fa.paged_decode_attention(
            q[0, :1], k[0, 7].reshape(1, 64), v[0, 7].reshape(1, 64), arena,
            arena, 0, jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.asarray([7], jnp.int32))
        assert out.shape == (1, 2, 32)

    def test_flash_refusal_propagates_on_tpu(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert fa.flash_attention_available() is True
        assert att.select_attention_impl(4096, 128) == "pallas"
        q, k, v = self._qkv()
        with pytest.raises(Exception) as exc:
            jax.block_until_ready(att.single_device_attention(
                q, k, v, causal=True, impl="pallas"))
        # the compiler's own words, not a warning and a dense result
        assert "interpret" in str(exc.value).lower() \
            or "pallas" in str(exc.value).lower()

    def test_requested_pallas_that_cannot_run_raises_on_tpu(
            self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        # t=200 tiles into 100-wide kv blocks Mosaic cannot lay out
        assert not fa.flash_attention_supported(200, 200, 64)
        with pytest.raises(ValueError, match="'pallas' requested"):
            att.select_attention_impl(200, 64, requested="pallas")

    def test_lrn_refusal_propagates_on_tpu(self, monkeypatch):
        from deeplearning4j_tpu.nn.layers.convolution import \
            LocalResponseNormalization
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        layer = LocalResponseNormalization(use_pallas=True)
        x = jnp.ones((1, 2, 2, 8), jnp.float32)
        with pytest.raises(Exception):
            jax.block_until_ready(layer.forward({}, {}, x)[0])

    def test_quant_arm_failure_propagates(self):
        # the TPU candidate set on this CPU host: the Pallas arm cannot
        # compile, and the measurement must say so, not serve "xla"
        assert "pallas" in pk._quant_candidates("tpu")
        with pytest.raises(Exception):
            pk._measure_quant_impl("tpu")

    def test_iteration_scalar_is_replicated_under_a_mesh(self):
        """Found by the smoke's four-device rehearsal: a single-device
        iteration scalar on step 1 cost a second full compile of the
        train step on step 2."""
        from deeplearning4j_tpu.nn.stepping import Trainer
        from deeplearning4j_tpu.parallel import data_parallel_mesh
        mesh = data_parallel_mesh(4)
        it = Trainer()._iteration_device(mesh)
        assert len(it.sharding.device_set) == 4
        assert it.sharding.is_fully_replicated
        assert np.asarray(it) == 0 and it.dtype == jnp.int32
