"""Autotune table: artifact roundtrip, version gating, graceful absence,
and the tuned-or-fallback routing contract in ``kernels.ops``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops
from repro.kernels import ref as kref


def _table(entries, backend=None, version=autotune.AUTOTUNE_VERSION):
    return {"version": version, "created": 0.0,
            "meta": {"backend": backend or jax.default_backend(),
                     "interpret": True, "smoke": True, "iters": 1},
            "entries": entries}


@pytest.fixture(autouse=True)
def _isolate_table():
    """Every test starts with no table and leaves none behind (conftest
    pins REPRO_AUTOTUNE=0, so reset re-reads that and disables)."""
    autotune.set_table(None)
    yield
    autotune.reset_table()


class TestArtifact:
    def test_roundtrip(self, tmp_path):
        key = autotune.shape_key("flash_decode", 100, 32, jnp.float32)
        payload = _table({key: {"backend": "kernel", "block_k": 64}})
        path = str(tmp_path / "autotune.json")
        autotune.save_artifact(payload, path)
        assert autotune.load_artifact(path) == payload

    def test_save_refuses_wrong_version(self, tmp_path):
        with pytest.raises(ValueError, match="version"):
            autotune.save_artifact(_table({}, version=99),
                                   str(tmp_path / "t.json"))

    def test_load_rejects_version_mismatch(self, tmp_path):
        path = str(tmp_path / "t.json")
        autotune.save_artifact(_table({}), path)
        import json
        payload = json.load(open(path))
        payload["version"] = autotune.AUTOTUNE_VERSION + 1
        json.dump(payload, open(path, "w"))
        with pytest.raises(ValueError, match="version"):
            autotune.load_artifact(path)

    def test_table_rejects_version_mismatch(self):
        with pytest.raises(ValueError, match="version"):
            autotune.AutotuneTable(_table({}, version=0))

    def test_absent_artifact_falls_back_gracefully(self, tmp_path,
                                                   monkeypatch):
        # a missing/unreadable artifact must leave routing on defaults,
        # never raise at kernel-call time
        monkeypatch.setenv("REPRO_AUTOTUNE",
                           str(tmp_path / "does_not_exist.json"))
        autotune.reset_table()
        assert autotune.get_table() is None
        assert autotune.lookup("flash_decode", 64, 32, jnp.float32) is None

    def test_stale_artifact_falls_back_gracefully(self, tmp_path,
                                                  monkeypatch):
        path = str(tmp_path / "stale.json")
        import json
        json.dump(_table({}, version=autotune.AUTOTUNE_VERSION + 1),
                  open(path, "w"))
        monkeypatch.setenv("REPRO_AUTOTUNE", path)
        autotune.reset_table()
        assert autotune.get_table() is None

    def test_env_disable(self, monkeypatch):
        monkeypatch.setenv("REPRO_AUTOTUNE", "0")
        autotune.reset_table()
        assert autotune.get_table() is None


class TestShapeKey:
    def test_seq_bucket_pow2(self):
        assert autotune.seq_bucket(1) == 64
        assert autotune.seq_bucket(64) == 64
        assert autotune.seq_bucket(65) == 128
        assert autotune.seq_bucket(100) == 128
        assert autotune.seq_bucket(1024) == 1024

    def test_key_normalizes_dtype(self):
        a = autotune.shape_key("ssd", 100, 16, jnp.float32)
        b = autotune.shape_key("ssd", 128, 16, np.float32)
        c = autotune.shape_key("ssd", 128, 16,
                               jnp.zeros((), jnp.float32).dtype)
        assert a == b == c == "ssd|s128|d16|float32"


class TestRouting:
    def _decode_args(self):
        rng = np.random.default_rng(0)
        b, s, h, d = 2, 64, 2, 32
        q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
        lengths = jnp.asarray([5, 64], jnp.int32)
        return q, k, v, lengths

    def test_ref_entry_routes_to_reference_bitwise(self):
        q, k, v, lengths = self._decode_args()
        key = autotune.shape_key("flash_decode", k.shape[1], q.shape[3],
                                 q.dtype)
        autotune.set_table(autotune.AutotuneTable(
            _table({key: {"backend": "ref"}})))
        out = ops.flash_decode(q, k, v, lengths)
        ref = kref.flash_decode_ref(q, k, v, lengths)
        assert (np.asarray(out) == np.asarray(ref)).all()

    def test_recording_names_each_route(self):
        """``ops.recording`` sees the interpreter on CPU and the reference
        route a tuned "ref" entry selects; calls outside stay unseen."""
        q, k, v, lengths = self._decode_args()
        ops.flash_decode(q, k, v, lengths, block_k=32)
        with ops.recording() as outer:
            ops.flash_decode(q, k, v, lengths, block_k=32)
            key = autotune.shape_key("flash_decode", k.shape[1],
                                     q.shape[3], q.dtype)
            autotune.set_table(autotune.AutotuneTable(
                _table({key: {"backend": "ref"}})))
            with ops.recording() as inner:
                ops.flash_decode(q, k, v, lengths)
        assert outer == {"flash_decode": {"interpret", "ref"}}
        assert inner == {"flash_decode": {"ref"}}

    def test_kernel_entry_supplies_blocks(self):
        q, k, v, lengths = self._decode_args()
        key = autotune.shape_key("flash_decode", k.shape[1], q.shape[3],
                                 q.dtype)
        autotune.set_table(autotune.AutotuneTable(
            _table({key: {"backend": "kernel", "block_k": 32}})))
        out = ops.flash_decode(q, k, v, lengths)
        ref = kref.flash_decode_ref(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_explicit_blocks_beat_ref_entry(self):
        """A caller-pinned block size must run the kernel even when the
        table says the reference wins at this shape."""
        q, k, v, lengths = self._decode_args()
        key = autotune.shape_key("flash_decode", k.shape[1], q.shape[3],
                                 q.dtype)
        autotune.set_table(autotune.AutotuneTable(
            _table({key: {"backend": "ref"}})))
        pinned = ops.flash_decode(q, k, v, lengths, block_k=64)
        autotune.set_table(None)
        bare = ops.flash_decode(q, k, v, lengths, block_k=64)
        assert (np.asarray(pinned) == np.asarray(bare)).all()

    def test_other_backend_table_is_ignored(self):
        q, k, v, lengths = self._decode_args()
        key = autotune.shape_key("flash_decode", k.shape[1], q.shape[3],
                                 q.dtype)
        other = "tpu" if jax.default_backend() != "tpu" else "cpu"
        table = autotune.AutotuneTable(
            _table({key: {"backend": "ref"}}, backend=other))
        assert table.lookup("flash_decode", k.shape[1], q.shape[3],
                            q.dtype) is None

    def test_ssd_ref_entry_matches_model_path(self):
        from repro.models.ssm import ssd_chunked
        rng = np.random.default_rng(1)
        b, s, h, p, n = 1, 64, 2, 16, 16
        x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
        dt = jax.nn.softplus(
            jnp.asarray(rng.standard_normal((b, s, h)), jnp.float32) - 1.0)
        A = -jnp.exp(jnp.asarray(rng.standard_normal((h,)), jnp.float32)
                     * 0.5)
        Bm = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
        Cm = jnp.asarray(rng.standard_normal((b, s, n)), jnp.float32)
        key = autotune.shape_key("ssd", s, p, x.dtype)
        autotune.set_table(autotune.AutotuneTable(
            _table({key: {"backend": "ref"}})))
        out = ops.ssd(x, dt, A, Bm, Cm)
        ref = ssd_chunked(x, dt, A, Bm, Cm)
        assert (np.asarray(out) == np.asarray(ref)).all()

    def test_attention_ref_entry_matches_model_path(self):
        from repro.models.attention import full_attention
        rng = np.random.default_rng(2)
        b, s, h, d = 1, 64, 2, 16
        q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)),
                               jnp.float32) for _ in range(3))
        key = autotune.shape_key("flash_attention", s, d, q.dtype)
        autotune.set_table(autotune.AutotuneTable(
            _table({key: {"backend": "ref"}})))
        out = ops.flash_attention(q, k, v)
        ref = full_attention(q, k, v, causal=True)
        assert (np.asarray(out) == np.asarray(ref)).all()


class TestSweep:
    def test_tiny_sweep_end_to_end(self, monkeypatch, tmp_path):
        """A minimal sweep produces a loadable table whose chosen config
        is never slower than the hard-coded default (the acceptance
        property), and ops picks it up through the env path."""
        monkeypatch.setattr(autotune, "SMOKE_ATTN_CLASSES", [(64, 8)])
        monkeypatch.setattr(autotune, "SMOKE_DECODE_CLASSES", [(64, 8)])
        monkeypatch.setattr(autotune, "SMOKE_PAGED_DECODE_CLASSES",
                            [(8, 8)])
        monkeypatch.setattr(autotune, "SMOKE_SSD_CLASSES", [(64, 8)])
        monkeypatch.setattr(autotune, "SMOKE_CANDIDATES", {
            "flash_attention": [(64, 64), (128, 128)],
            "flash_decode": [64, 128],
            "flash_decode_paged": [None],
            "ssd": [64, 256],
        })
        table, bench = autotune.run_autotune(smoke=True, iters=1)
        assert set(table["entries"]) == set(bench["entries"])
        assert any(k.startswith("flash_decode_paged|s8|")
                   for k in table["entries"])
        for key, e in table["entries"].items():
            assert e["speedup_vs_default"] >= 1.0, (key, e)
            assert e["t_best"] <= e["t_ref"]
            assert e["t_best"] <= e["t_default"]
            if e["backend"] == "ref":
                assert e["t_best"] == e["t_ref"]
        path = str(tmp_path / "autotune.json")
        autotune.save_artifact(table, path)
        monkeypatch.setenv("REPRO_AUTOTUNE", path)
        autotune.reset_table()
        loaded = autotune.get_table()
        assert loaded is not None
        assert loaded.lookup("flash_decode", 64, 8,
                             jnp.float32) is not None


class TestFlashDecodeNoClamp:
    def test_short_cache_pads_to_block(self):
        """s < block_k no longer silently clamps the block size: the
        cache pads up to one full block and the result is exact."""
        rng = np.random.default_rng(3)
        b, s, h, d = 2, 24, 2, 16
        q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
        lengths = jnp.asarray([3, 24], jnp.int32)
        out = ops.flash_decode(q, k, v, lengths, block_k=128)
        ref = kref.flash_decode_ref(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
