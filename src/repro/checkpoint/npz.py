"""Minimal npz pytree checkpointing: flatten with '/'-joined key paths,
save atomically (tmp file + fsync + rename), restore into the same tree
structure. A corrupted or truncated file raises :class:`CheckpointError`
with the path and cause, never a raw ``zipfile`` traceback — the
executor's recovery path (DESIGN.md §16) decides whether to fall back to
an older checkpoint or restart from scratch.

Every checkpoint carries a CRC32 **content** checksum (``__crc32__``,
computed over the sorted keys and raw array bytes, independent of zip
metadata): silent bit-rot that still parses as a valid npz — the failure
mode fsync+rename cannot catch — surfaces as :class:`CheckpointError`
on load instead of restarting training from corrupt state. The stored
CRC doubles as a cheap cross-process state digest: the fleet master
compares agents' checkpoint CRCs against the single-host executor's to
assert bit-exact recovery (DESIGN.md §17). Files written before the
checksum existed load unchecked."""
from __future__ import annotations

import json
import os
import tempfile
import zipfile
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["CheckpointError", "checkpoint_crc", "load_pytree", "restore",
           "save", "save_pytree"]

_CRC_KEY = "__crc32__"
# npz has no descriptor for extension dtypes (bfloat16 and friends): such
# leaves are stored as same-width unsigned views, with their dtype names
# recorded here so a load views them back bit for bit
_DTYPES_KEY = "__dtypes__"


class CheckpointError(RuntimeError):
    """A checkpoint file is unreadable (corrupted, truncated, or not an
    npz archive). Carries ``path`` so recovery code can report which
    file is damaged."""

    def __init__(self, path: str, reason: str) -> None:
        self.path = path
        super().__init__(f"checkpoint {path!r} is unreadable: {reason}")


def _flatten(tree) -> Dict[str, np.ndarray]:
    flat, ext = {}, {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(
            str(p.key) if isinstance(p, jax.tree_util.DictKey)
            else str(getattr(p, "idx", p)) for p in path)
        arr = np.asarray(leaf)
        if arr.dtype.str[1] == "V":          # no npz descriptor: store bits
            ext[key] = arr.dtype.name
            arr = arr.view(f"u{arr.dtype.itemsize}")
        flat[key] = arr
    if ext:
        flat[_DTYPES_KEY] = np.asarray(json.dumps(ext, sort_keys=True))
    return flat


def _content_crc(flat: Dict[str, np.ndarray]) -> int:
    """CRC32 over the flattened content in sorted-key order: each key,
    its dtype/shape, and the raw array bytes. Deterministic for equal
    content regardless of zip timestamps or member ordering."""
    crc = 0
    for key in sorted(flat):
        arr = np.ascontiguousarray(flat[key])
        for token in (key, str(arr.dtype), str(arr.shape)):
            crc = zlib.crc32(token.encode(), crc)
        crc = zlib.crc32(arr.tobytes(), crc)
    return crc & 0xFFFFFFFF


def save_pytree(path: str, tree) -> None:
    flat = _flatten(tree)
    flat[_CRC_KEY] = np.asarray(_content_crc(flat), dtype=np.uint32)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    # NOTE: np.savez appends ".npz" unless the name already ends with it
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp.npz")
    os.close(fd)
    try:
        np.savez(tmp, **flat)
        # fsync before rename: os.replace is atomic on the directory
        # entry, but a crash between write and flush could otherwise
        # publish a truncated file under the final name
        fd = os.open(tmp, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def load_pytree(path: str, like) -> Any:
    """Restore into the structure of ``like`` (a template pytree).

    Raises :class:`FileNotFoundError` if ``path`` does not exist and
    :class:`CheckpointError` if it exists but cannot be parsed.
    """
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    try:
        with np.load(path) as data:
            flat = {k: data[k] for k in data.files}
    except (zipfile.BadZipFile, ValueError, EOFError, OSError) as exc:
        raise CheckpointError(path, f"{type(exc).__name__}: {exc}") from exc
    stored = flat.pop(_CRC_KEY, None)
    if stored is not None:
        stored_crc = int(stored)
        computed = _content_crc(flat)
        if computed != stored_crc:
            raise CheckpointError(
                path, f"content CRC mismatch: stored {stored_crc:#010x}, "
                      f"computed {computed:#010x} (silent bit-rot)")
    ext = json.loads(str(flat.pop(_DTYPES_KEY, "{}")))
    for key, name in ext.items():
        flat[key] = flat[key].view(jnp.dtype(name))
    leaves_like, treedef = jax.tree_util.tree_flatten_with_path(like)
    out = []
    for path_keys, leaf in leaves_like:
        key = "/".join(
            str(p.key) if isinstance(p, jax.tree_util.DictKey)
            else str(getattr(p, "idx", p)) for p in path_keys)
        if key not in flat:
            raise KeyError(f"checkpoint missing {key!r}")
        arr = flat[key]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"{key}: shape {arr.shape} != {leaf.shape}")
        out.append(jnp.asarray(arr, dtype=leaf.dtype))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(like), out)


def checkpoint_crc(path: str) -> Optional[int]:
    """The stored content CRC of a checkpoint file (``None`` for files
    written before the checksum existed). Cheap — reads one tiny npz
    member — so the fleet layer uses it as the per-job state digest when
    comparing cross-process runs against the single-host executor."""
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    try:
        with np.load(path) as data:
            if _CRC_KEY not in data.files:
                return None
            return int(data[_CRC_KEY])
    except (zipfile.BadZipFile, ValueError, EOFError, OSError) as exc:
        raise CheckpointError(path, f"{type(exc).__name__}: {exc}") from exc


def save(path: str, *, params, opt_state=None, step: int = 0,
         extra: Optional[Dict] = None) -> None:
    tree = {"params": params, "step": jnp.asarray(step)}
    if opt_state is not None:
        tree["opt"] = opt_state
    if extra:
        tree["extra"] = extra
    save_pytree(path, tree)


def restore(path: str, *, params_like, opt_like=None) -> Tuple:
    like = {"params": params_like, "step": jnp.zeros((), jnp.int32)}
    if opt_like is not None:
        like["opt"] = opt_like
    tree = load_pytree(path, like)
    return (tree["params"], tree.get("opt"), int(tree["step"]))
