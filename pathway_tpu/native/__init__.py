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
import threading
from typing import Optional

from pathway_tpu.internals import config as _config
from pathway_tpu.internals import tracing

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
_load_lock = threading.Lock()
_INT32_P = ctypes.POINTER(ctypes.c_int32)
_INT64_P = ctypes.POINTER(ctypes.c_int64)


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
    """Build (if needed) and load the native library; None if unavailable.
    PATHWAY_DISABLE_NATIVE is read at every call: set after the library
    was loaded it still sends the next batch down the python path."""
    if _config.env("PATHWAY_DISABLE_NATIVE"):
        return None
    if _lib is None and not _build_failed:
        # one build a process: the pipeline's prep threads ask at the same
        # moment on a checkout's first batches, and two builds into one
        # temporary file left the loser on the python path for good
        with _load_lock:
            if _lib is None and not _build_failed:
                _build_and_load()
    return _lib


def _built(source: str, name: str, flags: list, timeout: int) -> str:
    """The path of `source` built as a shared library, kept in the cache
    directory under its digest; g++ runs where it is not there yet
    (counter `setup.native_builds`)."""
    with open(source, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so_path = os.path.join(_cache_dir(), f"{name}_{digest}.so")
    if not os.path.exists(so_path):
        tracing.add("setup.native_builds")
        tmp = so_path + f".tmp{os.getpid()}"
        subprocess.run(
            ["g++", *flags, "-shared", "-fPIC", "-std=c++17", source, "-o", tmp],
            check=True,
            capture_output=True,
            timeout=timeout,
        )
        os.replace(tmp, so_path)
    return so_path


def _build_and_load() -> None:
    global _lib, _build_failed
    try:
        with tracing.span("setup.native_load"):
            so_path = _built(
                _source_path("tokenizer.cpp"), "pw_native", ["-O3"], 120
            )
            lib = ctypes.CDLL(so_path)
        lib.tokenize_batch.restype = None
        lib.tokenize_batch.argtypes = [
            ctypes.c_char_p, _INT64_P, _INT64_P,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            _INT32_P, _INT32_P,
        ]
        lib.first_fit.restype = None
        lib.first_fit.argtypes = [
            _INT32_P, _INT64_P,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            _INT32_P, _INT32_P, _INT32_P,
        ]
        lib.count_tokens.restype = ctypes.c_int32
        lib.count_tokens.argtypes = [ctypes.c_char_p, ctypes.c_int32]
        _lib = lib
    except Exception as exc:  # noqa: BLE001 — fall back to python
        _build_failed = True
        _log_build_failure("tokenizer", exc)


def tokenize_batch_native(texts, rows, vocab_size: int, ids, lengths) -> None:
    """Tokenises the ASCII `texts` in one call that holds no interpreter
    lock: text t's ids go to row `rows[t]` of the caller's C-contiguous
    int32 `ids` [n, seq_len] and their count to `lengths[rows[t]]`; other
    rows are not touched.  The library must be loaded (`load()`)."""
    import numpy as np

    n = len(texts)
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    for array, ndim in ((ids, 2), (lengths, 1)):
        if (
            array.dtype != np.int32
            or array.ndim != ndim
            or not array.flags.c_contiguous
            or not array.flags.writeable
        ):
            raise ValueError("ids and lengths must be writable C-contiguous int32")
    if rows.shape != (n,) or lengths.shape[0] != ids.shape[0] or (
        n and not (0 <= rows.min() and rows.max() < ids.shape[0])
    ):
        raise ValueError("rows must name one row of ids for each text")
    buffer = "".join(texts).encode("ascii")
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.fromiter(map(len, texts), dtype=np.int64, count=n), out=offsets[1:])
    _lib.tokenize_batch(
        buffer,
        offsets.ctypes.data_as(_INT64_P),
        rows.ctypes.data_as(_INT64_P),
        n,
        vocab_size,
        ids.shape[1],
        ids.ctypes.data_as(_INT32_P),
        lengths.ctypes.data_as(_INT32_P),
    )


def first_fit_native(lengths, order, slab: int, max_segments: int):
    """`pack_batch`'s placement in the library: (row, segment, first slot)
    of every document, int32 arrays, for int32 `lengths` placed in the
    order `order` (a permutation of their indices).  The library must be
    loaded (`load()`)."""
    import numpy as np

    n = len(lengths)
    lengths = np.ascontiguousarray(lengths, dtype=np.int32)
    order = np.ascontiguousarray(order, dtype=np.int64)
    if lengths.shape != (n,) or order.shape != (n,) or (
        n and not (0 <= order.min() and order.max() < n)
    ):
        raise ValueError("order must name each of the lengths")
    placed = np.zeros((3, n), dtype=np.int32)
    _lib.first_fit(
        lengths.ctypes.data_as(_INT32_P),
        order.ctypes.data_as(_INT64_P),
        n,
        slab,
        max_segments,
        *(row.ctypes.data_as(_INT32_P) for row in placed),
    )
    return tuple(placed)


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

        with tracing.span("setup.native_load"):
            so_path = _built(
                _source_path("wire_ext.cpp"), "pw_wire_ext",
                ["-O2", f"-I{sysconfig.get_path('include')}"], 180,
            )
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
