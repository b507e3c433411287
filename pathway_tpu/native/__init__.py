"""Native (C++) runtime components, built on demand via the system
toolchain and loaded with ctypes.

The reference keeps its hot runtime in Rust (src/engine, src/connectors);
here the compute hot path is XLA, and the native layer covers the host-side
feeding work that would otherwise bottleneck the chip — currently the batch
tokenizer. Falls back to the pure-python implementations when the build
fails, and says so once in the log with the compiler's own words.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
from typing import Optional

from pathway_tpu.internals import config as _config

logger = logging.getLogger(__name__)


def _log_build_failure(what: str, exc: BaseException) -> None:
    stderr = getattr(exc, "stderr", None)
    if isinstance(stderr, bytes):
        stderr = stderr.decode(errors="replace")
    logger.warning(
        "native %s unavailable, using the pure-python path: %s: %s%s",
        what,
        type(exc).__name__,
        exc,
        f"\n{stderr[-2000:]}" if stderr else "",
    )


_lib = None
_build_failed = False


def _source_path(name: str) -> str:
    return os.path.join(os.path.dirname(__file__), name)


def _cache_dir() -> str:
    root = _config.env("PATHWAY_NATIVE_CACHE") or os.path.join(
        os.environ.get("XDG_CACHE_HOME", os.path.expanduser("~/.cache")),
        "pathway_tpu",
    )
    os.makedirs(root, exist_ok=True)
    return root


def load() -> Optional[ctypes.CDLL]:
    """Build (if needed) and load the native library; None if unavailable."""
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    if _build_failed or _config.env("PATHWAY_DISABLE_NATIVE"):
        return None
    source = _source_path("tokenizer.cpp")
    try:
        with open(source, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        so_path = os.path.join(_cache_dir(), f"pw_native_{digest}.so")
        if not os.path.exists(so_path):
            tmp = so_path + f".tmp{os.getpid()}"
            subprocess.run(
                [
                    "g++",
                    "-O3",
                    "-shared",
                    "-fPIC",
                    "-std=c++17",
                    source,
                    "-o",
                    tmp,
                ],
                check=True,
                capture_output=True,
                timeout=120,
            )
            os.replace(tmp, so_path)
        lib = ctypes.CDLL(so_path)
        lib.tokenize_batch.restype = ctypes.c_int32
        lib.tokenize_batch.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.count_tokens.restype = ctypes.c_int32
        lib.count_tokens.argtypes = [ctypes.c_char_p, ctypes.c_int32]
        _lib = lib
        return lib
    except Exception as exc:  # noqa: BLE001 — fall back to python
        _build_failed = True
        _log_build_failure("tokenizer", exc)
        return None


def tokenize_batch_native(texts, vocab_size: int, seq_len: int):
    """Returns (ids, mask) int32 [n, seq_len] numpy arrays, or None when
    the native library is unavailable."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    encoded = [t.encode("utf-8", errors="replace") for t in texts]
    buffer = b"".join(encoded)
    offsets = np.zeros(len(texts) + 1, dtype=np.int64)
    np.cumsum([len(e) for e in encoded], out=offsets[1:])
    n = len(texts)
    ids = np.zeros((n, seq_len), dtype=np.int32)
    mask = np.zeros((n, seq_len), dtype=np.int32)
    lib.tokenize_batch(
        buffer,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n,
        vocab_size,
        seq_len,
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        mask.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return ids, mask


def count_tokens_native(text: str) -> Optional[int]:
    lib = load()
    if lib is None:
        return None
    data = text.encode("utf-8", errors="replace")
    return lib.count_tokens(data, len(data))


# -- CPython extension modules ----------------------------------------------

_wire_ext = None
_wire_ext_failed = False


def load_wire_ext():
    """Build (if needed) and import the native wire codec extension
    (native/wire_ext.cpp); None when the toolchain is unavailable. The
    extension is registered with the engine's value classes so it can
    construct Pointers/Json and delegate rare types back to the python
    codec."""
    global _wire_ext, _wire_ext_failed
    if _wire_ext is not None:
        return _wire_ext
    if _wire_ext_failed or _config.env("PATHWAY_DISABLE_NATIVE"):
        return None
    try:
        import importlib.machinery
        import importlib.util
        import sysconfig

        source = _source_path("wire_ext.cpp")
        with open(source, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        so_path = os.path.join(_cache_dir(), f"pw_wire_ext_{digest}.so")
        if not os.path.exists(so_path):
            tmp = so_path + f".tmp{os.getpid()}"
            subprocess.run(
                [
                    "g++",
                    "-O2",
                    "-shared",
                    "-fPIC",
                    "-std=c++17",
                    f"-I{sysconfig.get_path('include')}",
                    source,
                    "-o",
                    tmp,
                ],
                check=True,
                capture_output=True,
                timeout=180,
            )
            os.replace(tmp, so_path)
        loader = importlib.machinery.ExtensionFileLoader(
            "pw_wire_ext", so_path
        )
        spec = importlib.util.spec_from_loader("pw_wire_ext", loader)
        mod = importlib.util.module_from_spec(spec)
        loader.exec_module(mod)

        from pathway_tpu.engine import value as _value
        from pathway_tpu.engine import wire as _wire

        def encode_rare(v) -> bytes:
            out = bytearray()
            _wire.encode_value(out, v)
            return bytes(out)

        def decode_rare(tag: int, frame: bytes, offset: int):
            # zero-copy: read straight out of the whole frame at offset
            r = _wire._Reader(frame, offset)
            v = _wire.decode_value(r, _tag=tag)
            return v, r.pos - offset

        mod.register_types(
            _value.Pointer,
            _value.Json,
            _value.ERROR,
            _value.Error,
            _value.Pending,
            encode_rare,
            decode_rare,
            _wire.WireError,
        )
        _wire_ext = mod
        return mod
    except Exception as exc:  # noqa: BLE001 — fall back to the python codec
        _wire_ext_failed = True
        _log_build_failure("wire codec", exc)
        return None
