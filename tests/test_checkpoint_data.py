"""Checkpoint roundtrip + synthetic data pipeline determinism."""
import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_pytree, restore, save, save_pytree
from repro.configs import get_config
from repro.data import SyntheticLM, make_batch
from repro.models import init_params
from repro.train import adamw_init


def test_checkpoint_roundtrip():
    cfg = dataclasses.replace(get_config("whisper-tiny").reduced(),
                              dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ck.npz")
        save(path, params=params, opt_state=opt, step=7)
        p2, o2, step = restore(path, params_like=params, opt_like=opt)
        assert step == 7
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(opt), jax.tree.leaves(o2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_shape_mismatch_raises():
    tree = {"a": jnp.ones((3, 4))}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.npz")
        save_pytree(path, tree)
        import pytest
        with pytest.raises(ValueError):
            load_pytree(path, {"a": jnp.ones((4, 3))})


def test_data_determinism_and_labels():
    cfg = get_config("minicpm-2b").reduced()
    b1 = make_batch(cfg, 4, 32, step=5, seed=1)
    b2 = make_batch(cfg, 4, 32, step=5, seed=1)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    # labels are next-token shifted
    full = make_batch(cfg, 4, 32, step=0, seed=0)
    assert (np.asarray(full["tokens"][:, 1:])
            == np.asarray(full["labels"][:, :-1])).all()
    # iterator yields different steps
    it = iter(SyntheticLM(cfg, 4, 32, seed=0))
    a, b = next(it), next(it)
    assert not (np.asarray(a["tokens"]) == np.asarray(b["tokens"])).all()


def test_modality_stubs_present():
    vlm = get_config("qwen2-vl-2b").reduced()
    audio = get_config("whisper-tiny").reduced()
    bv = make_batch(vlm, 2, 32)
    ba = make_batch(audio, 2, 32)
    assert bv["vision_embeds"].shape == (2, vlm.vision_tokens, vlm.d_model)
    assert ba["frames"].shape == (2, audio.encoder_seq, audio.d_model)


# ====================================================================== #
# Corrupted / missing checkpoint files (DESIGN.md §16)
# ====================================================================== #
def test_load_missing_file_raises_filenotfound(tmp_path):
    import pytest

    from repro.checkpoint import CheckpointError  # noqa: F401  (re-export)
    with pytest.raises(FileNotFoundError):
        load_pytree(str(tmp_path / "nope.npz"), {"a": jnp.ones((2,))})


def test_load_corrupted_file_raises_checkpoint_error(tmp_path):
    import pytest

    from repro.checkpoint import CheckpointError
    path = tmp_path / "garbage.npz"
    path.write_bytes(b"this is not an npz archive")
    with pytest.raises(CheckpointError) as ei:
        load_pytree(str(path), {"a": jnp.ones((2,))})
    assert ei.value.path == str(path)
    assert str(path) in str(ei.value)


def test_load_truncated_file_raises_checkpoint_error(tmp_path):
    import pytest

    from repro.checkpoint import CheckpointError
    path = tmp_path / "trunc.npz"
    save_pytree(str(path), {"a": jnp.arange(4096, dtype=jnp.float32)})
    raw = path.read_bytes()
    path.write_bytes(raw[:len(raw) // 2])
    with pytest.raises(CheckpointError):
        load_pytree(str(path), {"a": jnp.arange(4096, dtype=jnp.float32)})


def test_bit_rot_detected_by_content_crc(tmp_path):
    """A flipped bit inside a still-valid npz archive (the failure mode
    atomicity cannot catch) raises CheckpointError on load instead of
    silently restoring corrupt state."""
    import pytest

    from repro.checkpoint import CheckpointError
    path = tmp_path / "rot.npz"
    tree = {"a": jnp.arange(64, dtype=jnp.float32),
            "b": {"c": jnp.ones((4, 4))}}
    save_pytree(str(path), tree)
    # re-save the archive with one array element flipped but the ORIGINAL
    # stored CRC — a parseable-but-rotten file
    with np.load(str(path)) as data:
        members = {k: data[k].copy() for k in data.files}
    members["a"][17] += 1.0
    np.savez(str(path), **members)
    with pytest.raises(CheckpointError, match="CRC mismatch"):
        load_pytree(str(path), tree)


def test_checkpoint_crc_is_a_content_digest(tmp_path):
    """Equal content -> equal stored CRC (independent of write time and
    path); different content -> different CRC. This is the digest the
    fleet layer compares across processes."""
    from repro.checkpoint import checkpoint_crc
    tree = {"a": jnp.arange(8, dtype=jnp.float32), "step": jnp.asarray(3)}
    p1, p2, p3 = (str(tmp_path / n) for n in ("x.npz", "y.npz", "z.npz"))
    save_pytree(p1, tree)
    save_pytree(p2, tree)
    save_pytree(p3, {**tree, "step": jnp.asarray(4)})
    c1, c2, c3 = map(checkpoint_crc, (p1, p2, p3))
    assert c1 == c2 and c1 is not None
    assert c3 != c1
    # loading a checksummed file still round-trips
    out = load_pytree(p1, tree)
    assert (np.asarray(out["a"]) == np.arange(8)).all()


def test_legacy_checkpoint_without_crc_loads_unchecked(tmp_path):
    from repro.checkpoint import checkpoint_crc
    path = tmp_path / "legacy.npz"
    np.savez(str(path), a=np.arange(4, dtype=np.float32))
    assert checkpoint_crc(str(path)) is None
    out = load_pytree(str(path), {"a": jnp.zeros((4,), jnp.float32)})
    assert (np.asarray(out["a"]) == np.arange(4)).all()


def test_save_pytree_is_atomic_no_tmp_left(tmp_path):
    path = tmp_path / "ck.npz"
    save_pytree(str(path), {"a": jnp.ones((3,))})
    save_pytree(str(path), {"a": jnp.zeros((3,))})   # overwrite in place
    assert sorted(p.name for p in tmp_path.iterdir()) == ["ck.npz"]
    out = load_pytree(str(path), {"a": jnp.ones((3,))})
    assert (np.asarray(out["a"]) == 0).all()


def test_bfloat16_leaves_roundtrip_bit_exactly(tmp_path):
    """npz has no bfloat16 descriptor: the leaves travel as uint16 bit
    views and load back as the same bfloat16 values, CRC-verified."""
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 5),
                                   jnp.bfloat16),
            "f": jnp.arange(3, dtype=jnp.float32)}
    path = str(tmp_path / "bf16.npz")
    save_pytree(path, tree)
    got = load_pytree(path, tree)
    assert got["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got["w"]).view(np.uint16),
                                  np.asarray(tree["w"]).view(np.uint16))
    np.testing.assert_array_equal(np.asarray(got["f"]),
                                  np.asarray(tree["f"]))
