"""ctypes bindings to the tdx-tpu native core (libtdxgraph.so).

The reference exposes its C++ core through a pybind11 extension
(``torchdistx._C``, reference src/python/torchdistx/_C/module.cc).  pybind11
is unavailable in this environment, so the native core speaks a flat C ABI
and this module is the binding layer.  The shared library is git-ignored:
on first import (fresh checkout, ``git archive`` export) it is compiled
with the checked-in Makefile — a single translation unit, about a second —
and it is rebuilt whenever ``graph.cc`` no longer hashes to what the
library was built from.  The hash, not the mtime, decides: a copied tree
carries a stale library with fresh timestamps.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_CSRC = os.path.join(os.path.dirname(_HERE), "csrc")
# TDX_NATIVE_LIB selects a sanitizer build (e.g. libtdxgraph-asan.so built
# with `make SANITIZE=asan`) — see scripts/run-sanitized-tests.
_LIB_NAME = os.environ.get("TDX_NATIVE_LIB", "libtdxgraph.so")
_LIB_PATH = os.path.join(_HERE, _LIB_NAME)
_STAMP_PATH = _LIB_PATH + ".srchash"  # what the library was built from

_build_lock = threading.Lock()


def _build() -> None:
    # -B: the source hash already said "rebuild"; make's mtime rule may
    # disagree.  The Makefile links to a temporary name and renames, so a
    # concurrent importer never sees a partial library.
    cmd = ["make", "-s", "-B", "-C", _CSRC]
    for sanitizer in ("asan", "ubsan", "tsan"):
        if _LIB_NAME.endswith(f"-{sanitizer}.so"):
            cmd.append(f"SANITIZE={sanitizer}")
            break
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"building the tdx native core failed "
            f"(command: {' '.join(cmd)}):\n{proc.stdout}\n{proc.stderr}"
        )


def _source_hash():
    """sha256 of the native sources, or None without them (an installed
    wheel ships the built library only)."""
    h = hashlib.sha256()
    try:
        for name in ("graph.cc", "Makefile"):
            with open(os.path.join(_CSRC, name), "rb") as f:
                h.update(f.read())
    except FileNotFoundError:
        return None
    return h.hexdigest()


def _read_stamp():
    try:
        with open(_STAMP_PATH) as f:
            return f.read().strip()
    except FileNotFoundError:
        return None


def _load() -> ctypes.CDLL:
    with _build_lock:
        want = _source_hash()
        if not os.path.exists(_LIB_PATH) or (
            want is not None and _read_stamp() != want
        ):
            _build()
            tmp = f"{_STAMP_PATH}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                f.write(want or "")
            os.replace(tmp, _STAMP_PATH)
    return ctypes.CDLL(_LIB_PATH)


_lib = _load()

_i64 = ctypes.c_int64
_i32 = ctypes.c_int32
_i64p = ctypes.POINTER(ctypes.c_int64)
_i32p = ctypes.POINTER(ctypes.c_int32)

_lib.tdx_graph_new.restype = ctypes.c_void_p
_lib.tdx_graph_new.argtypes = []
_lib.tdx_graph_free.restype = None
_lib.tdx_graph_free.argtypes = [ctypes.c_void_p]
_lib.tdx_record_op.restype = _i64
_lib.tdx_record_op.argtypes = [ctypes.c_void_p, ctypes.c_char_p, _i64p, _i64, _i32]
_lib.tdx_set_output_meta.restype = None
_lib.tdx_set_output_meta.argtypes = [ctypes.c_void_p, _i64, _i32, _i64p, _i32, _i32]
_lib.tdx_get_output_meta.restype = _i32
_lib.tdx_get_output_meta.argtypes = [ctypes.c_void_p, _i64, _i32, _i64p, _i32, _i32p]
_lib.tdx_collect_schedule.restype = _i64
_lib.tdx_collect_schedule.argtypes = [ctypes.c_void_p, _i64, _i64p, _i64]
_lib.tdx_mark_materialized.restype = _i64
_lib.tdx_mark_materialized.argtypes = [ctypes.c_void_p, _i64, _i64p, _i64]
_lib.tdx_node_state.restype = _i32
_lib.tdx_node_state.argtypes = [ctypes.c_void_p, _i64]
_lib.tdx_pin.restype = None
_lib.tdx_pin.argtypes = [ctypes.c_void_p, _i64]
_lib.tdx_unpin.restype = _i32
_lib.tdx_unpin.argtypes = [ctypes.c_void_p, _i64]
_lib.tdx_num_nodes.restype = _i64
_lib.tdx_num_nodes.argtypes = [ctypes.c_void_p]
_lib.tdx_num_materialized.restype = _i64
_lib.tdx_num_materialized.argtypes = [ctypes.c_void_p]
_lib.tdx_num_released.restype = _i64
_lib.tdx_num_released.argtypes = [ctypes.c_void_p]
_lib.tdx_get_deps.restype = _i64
_lib.tdx_get_deps.argtypes = [ctypes.c_void_p, _i64, _i64p, _i64]
_lib.tdx_get_dependents.restype = _i64
_lib.tdx_get_dependents.argtypes = [ctypes.c_void_p, _i64, _i64p, _i64]
_lib.tdx_get_name.restype = _i64
_lib.tdx_get_name.argtypes = [ctypes.c_void_p, _i64, ctypes.c_char_p, _i64]

NODE_RECORDED = 0
NODE_MATERIALIZED = 1
NODE_RELEASED = 2


class NativeGraph:
    """Thin OO wrapper over the C ABI.  One instance per recording session."""

    def __init__(self) -> None:
        self._h = _lib.tdx_graph_new()

    def __del__(self) -> None:
        h = getattr(self, "_h", None)
        if h:
            _lib.tdx_graph_free(h)
            self._h = None

    def record_op(self, name: str, deps: list[int], n_outputs: int) -> int:
        arr = (ctypes.c_int64 * max(len(deps), 1))(*deps)
        nid = _lib.tdx_record_op(
            self._h, name.encode(), arr, len(deps), n_outputs
        )
        if nid < 0:
            raise RuntimeError(
                f"native graph rejected op {name!r}: a dependency was already"
                " released (recording on a garbage-collected node)"
            )
        return nid

    def set_output_meta(
        self, node: int, out_idx: int, dims: tuple[int, ...], dtype_code: int
    ) -> None:
        arr = (ctypes.c_int64 * max(len(dims), 1))(*dims)
        _lib.tdx_set_output_meta(
            self._h, node, out_idx, arr, len(dims), dtype_code
        )

    def get_output_meta(self, node: int, out_idx: int) -> tuple[tuple[int, ...], int]:
        cap = 16
        dims = (ctypes.c_int64 * cap)()
        code = ctypes.c_int32()
        rank = _lib.tdx_get_output_meta(
            self._h, node, out_idx, dims, cap, ctypes.byref(code)
        )
        if rank < 0:
            raise KeyError(f"no metadata for node {node} output {out_idx}")
        return tuple(dims[:rank]), code.value

    def collect_schedule(self, target: int) -> list[int]:
        cap = 1024
        while True:
            buf = (ctypes.c_int64 * cap)()
            n = _lib.tdx_collect_schedule(self._h, target, buf, cap)
            if n == -1:
                cap *= 8
                continue
            if n == -2:
                raise RuntimeError(
                    f"cannot materialize node {target}: unknown node or a"
                    " required dependency was already released"
                )
            return list(buf[:n])

    def mark_materialized(self, node: int) -> list[int]:
        cap = 64
        while True:
            buf = (ctypes.c_int64 * cap)()
            n = _lib.tdx_mark_materialized(self._h, node, buf, cap)
            if n < 0:  # -(needed count): retry with a big-enough buffer
                cap = -n
                continue
            return list(buf[:n])

    def node_state(self, node: int) -> int:
        return _lib.tdx_node_state(self._h, node)

    def pin(self, node: int) -> None:
        # _h can be None if cyclic GC finalized the graph first (the native
        # side also tolerates NULL; both guards keep finalizer races benign)
        if self._h:
            _lib.tdx_pin(self._h, node)

    def unpin(self, node: int) -> bool:
        if not self._h:
            return False
        return bool(_lib.tdx_unpin(self._h, node))

    def num_nodes(self) -> int:
        return _lib.tdx_num_nodes(self._h)

    def num_materialized(self) -> int:
        return _lib.tdx_num_materialized(self._h)

    def num_released(self) -> int:
        return _lib.tdx_num_released(self._h)

    def _read_ids(self, c_fn, node: int) -> list[int]:
        cap = 256
        while True:
            buf = (ctypes.c_int64 * cap)()
            n = c_fn(self._h, node, buf, cap)
            if n == -2:
                raise KeyError(f"unknown node {node}")
            if n == -1:
                cap *= 8
                continue
            return list(buf[:n])

    def deps(self, node: int) -> list[int]:
        return self._read_ids(_lib.tdx_get_deps, node)

    def dependents(self, node: int) -> list[int]:
        return self._read_ids(_lib.tdx_get_dependents, node)

    def name(self, node: int) -> str:
        cap = 512
        buf = ctypes.create_string_buffer(cap)
        n = _lib.tdx_get_name(self._h, node, buf, cap)
        if n < 0:
            return ""
        return buf.value.decode()
