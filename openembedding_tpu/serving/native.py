"""ctypes bindings for the native serving runtime (native/oe_serving.cc).

The reference serves inference through a packed C++ library so TF-Serving
needs no Python (entry/c_api.h exb_* ABI + libcexb_pack.so); here the same
role is a small dependency-free C++17 library that memory-maps a checkpoint
directory and answers read-only pulls. These bindings exist for tests and
for Python hosts that want the zero-JAX lookup path; C++ serving stacks
link ``liboe_serving.so`` directly against ``native/oe_serving.h``.

Build: ``make -C native`` (g++ only, no dependencies).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Any, Optional, Sequence

import numpy as np

# stdlib-only observability: the zero-JAX lookup path stays zero-JAX
# (scope + observability import nothing heavier than numpy)
from ..analysis import scope

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "liboe_serving.so")


def build_library(variant: str = "") -> str:
    """``make`` liboe_serving.so and return its path. Always through make
    (it is incremental): a ``.so`` already on disk may be older than the
    tracked ``oe_serving.cc``, and the library served must be the one the
    tracked source builds.

    ``variant`` selects a sanitizer build for the graftfuzz gate:
    ``"asan"`` / ``"ubsan"`` compile ``liboe_serving_<variant>.so`` via
    the Makefile's matching target. ASan probes must run in a process
    that LD_PRELOADs libasan.so (gcc does not link the ASan runtime
    into shared objects) — analysis/fuzz.py handles that; don't dlopen
    the asan .so into a long-lived host process.
    """
    if variant not in ("", "asan", "ubsan"):
        raise ValueError(f"unknown native build variant {variant!r}")
    lib_path = (os.path.join(_NATIVE_DIR, f"liboe_serving_{variant}.so")
                if variant else _LIB_PATH)
    if not os.path.isdir(_NATIVE_DIR):
        raise RuntimeError(
            "native/ sources not found — the native serving library builds "
            "from a source checkout (make -C native); from an installed "
            "package, build it there and pass lib_path to NativeModel")
    target = ["make", "-C", _NATIVE_DIR] + ([variant] if variant else [])
    try:
        subprocess.run(target, check=True, capture_output=True, text=True)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"native build failed:\n{e.stdout}\n{e.stderr}") from e
    return lib_path


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.oe_last_error.restype = ctypes.c_char_p
    lib.oe_model_load.restype = ctypes.c_void_p
    lib.oe_model_load.argtypes = [ctypes.c_char_p]
    lib.oe_model_free.argtypes = [ctypes.c_void_p]
    lib.oe_model_sign.restype = ctypes.c_char_p
    lib.oe_model_sign.argtypes = [ctypes.c_void_p]
    lib.oe_model_num_variables.restype = ctypes.c_int
    lib.oe_model_num_variables.argtypes = [ctypes.c_void_p]
    lib.oe_model_variable.restype = ctypes.c_void_p
    lib.oe_model_variable.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.oe_model_variable_by_id.restype = ctypes.c_void_p
    lib.oe_model_variable_by_id.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.oe_variable_name.restype = ctypes.c_char_p
    lib.oe_variable_name.argtypes = [ctypes.c_void_p]
    lib.oe_variable_dim.restype = ctypes.c_int
    lib.oe_variable_dim.argtypes = [ctypes.c_void_p]
    lib.oe_variable_vocab.restype = ctypes.c_int64
    lib.oe_variable_vocab.argtypes = [ctypes.c_void_p]
    lib.oe_variable_rows.restype = ctypes.c_int64
    lib.oe_variable_rows.argtypes = [ctypes.c_void_p]
    lib.oe_pull_weights.restype = ctypes.c_int
    lib.oe_pull_weights.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float)]
    lib.oe_model_version.restype = ctypes.c_int64
    lib.oe_model_version.argtypes = [ctypes.c_void_p]
    lib.oe_pull_weights_gather.restype = ctypes.c_int
    lib.oe_pull_weights_gather.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float)]
    return lib


class NativeModel:
    """A checkpoint served by the native library (read-only lookups)."""

    def __init__(self, path: str, lib_path: Optional[str] = None):
        self._lib = _bind(ctypes.CDLL(lib_path or build_library()))
        self._model = self._lib.oe_model_load(path.encode())
        if not self._model:
            raise RuntimeError(
                f"native load failed: {self._lib.oe_last_error().decode()}")

    def close(self) -> None:
        if self._model:
            self._lib.oe_model_free(self._model)
            self._model = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    @property
    def sign(self) -> str:
        return self._lib.oe_model_sign(self._model).decode()

    @property
    def version(self) -> int:
        """Delta-chain seq this load replayed up to (0 for plain full
        dumps) — ``checkpoint_delta.applied_seq`` semantics: the native
        reader resolves ``delta_manifest`` chains directly at open, so
        a delta-compacted dir serves WITHOUT a prior full save."""
        return int(self._lib.oe_model_version(self._model))

    @property
    def num_variables(self) -> int:
        return self._lib.oe_model_num_variables(self._model)

    def _var(self, variable) -> ctypes.c_void_p:
        if isinstance(variable, int):
            v = self._lib.oe_model_variable_by_id(self._model, variable)
        else:
            v = self._lib.oe_model_variable(self._model, variable.encode())
        if not v:
            raise KeyError(self._lib.oe_last_error().decode())
        return v

    def variable_dim(self, variable) -> int:
        return self._lib.oe_variable_dim(self._var(variable))

    def variable_vocab(self, variable) -> int:
        return self._lib.oe_variable_vocab(self._var(variable))

    @staticmethod
    def _join_keys(arr: np.ndarray) -> np.ndarray:
        """Wide [..., 2] int32 pairs -> joined 64-bit values (the native
        index is keyed by joined ids); other arrays pass through."""
        if arr.ndim >= 2 and arr.shape[-1] == 2 and arr.dtype == np.int32:
            from .. import hash_table as hash_lib
            return hash_lib.join64(arr)
        return arr

    def lookup(self, variable, keys: Sequence[int]) -> np.ndarray:
        """Read-only pull: [n] keys -> [n, dim] float32 rows (missing/
        invalid keys -> zero rows). Wide [n, 2] int32 pair keys (the
        framework's x64-off representation) are joined to their 64-bit
        values — the native index is keyed by joined ids."""
        v = self._var(variable)
        dim = self._lib.oe_variable_dim(v)
        # resolve the NAME for the metric label (like the registry
        # path): an id-based lookup(0, ...) must not split the same
        # table's series into table="0" vs table="emb"
        name = self._lib.oe_variable_name(v).decode()
        arr = np.asarray(keys)
        # record BEFORE the wide-pair join: the registry path records
        # the raw element count (2n for [n, 2] pairs — wire volume),
        # and both paths must feed the same units into one series
        from ..utils.observability import record_serving_lookup
        record_serving_lookup(name, arr.size)
        arr = self._join_keys(arr)
        k = np.ascontiguousarray(arr.astype(np.int64).ravel())
        out = np.zeros((k.size, dim), np.float32)
        # request-scoped span: the native leg of a traced serving
        # request (graftload --path native) lands in the same Perfetto
        # trace as the REST legs
        with scope.span("serving.native_lookup", table=name):
            rc = self._lib.oe_pull_weights(
                v, k.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                k.size,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        if rc != 0:
            raise RuntimeError(self._lib.oe_last_error().decode())
        # batch shape AFTER the join: pair inputs collapse their last axis
        return out.reshape(arr.shape + (dim,))

    def pull_gather(self, variable, unique_keys: np.ndarray,
                    gather: np.ndarray) -> np.ndarray:
        """The batched C entry point (``oe_pull_weights_gather``): each
        UNIQUE key probes the native index exactly once, rows scatter
        to ``out[i] = row(unique_keys[gather[i]])`` in one call — the
        micro-batcher's data plane on the mmap path."""
        v = self._var(variable)
        dim = self._lib.oe_variable_dim(v)
        name = self._lib.oe_variable_name(v).decode()
        uniq = np.ascontiguousarray(
            self._join_keys(np.asarray(unique_keys))
            .astype(np.int64).ravel())
        gidx = np.ascontiguousarray(np.asarray(gather, np.int64).ravel())
        out = np.zeros((gidx.size, dim), np.float32)
        with scope.span("serving.native_lookup_batched", table=name):
            rc = self._lib.oe_pull_weights_gather(
                v, uniq.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                uniq.size,
                gidx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                gidx.size,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        if rc != 0:
            raise RuntimeError(self._lib.oe_last_error().decode())
        return out

    def lookup_batched(self, variable, requests) -> list:
        """Resolve SEVERAL flat key arrays with ONE deduped native call:
        concatenate, dedup, one ``oe_pull_weights_gather``, split rows
        back per request. The in-process coalescing primitive the
        native micro-batcher flushes through."""
        from . import batcher as batcher_mod
        from ..utils.observability import record_serving_lookup
        name = (variable if isinstance(variable, str)
                else self._lib.oe_variable_name(
                    self._var(variable)).decode())
        arrs = [np.asarray(r) for r in requests]
        for a in arrs:
            record_serving_lookup(name, a.size)
        joined = [self._join_keys(a) for a in arrs]
        cat = np.concatenate([j.astype(np.int64).ravel()
                              for j in joined]) if joined \
            else np.zeros(0, np.int64)
        uniq, inverse = batcher_mod.dedup_keys(cat)
        rows = self.pull_gather(name, uniq, inverse)
        out = []
        off = 0
        for j in joined:
            n = int(np.prod(j.shape, dtype=np.int64)) if j.ndim else 1
            out.append(rows[off:off + n]
                       .reshape(j.shape + (rows.shape[1],)))
            off += n
        return out

    def make_batcher(self, **cfg) -> "Any":
        """A :class:`~..serving.batcher.LookupBatcher` over this model:
        concurrent native lookups coalesce into one
        ``oe_pull_weights_gather`` per flush. The mmap view is
        immutable after open, so the snapshot hook is trivial."""
        from .batcher import LookupBatcher

        def _pull_scatter(_snap, name, uniq, inverse):
            return self.pull_gather(name, uniq, inverse)

        return LookupBatcher(self.sign or "native", lambda: None,
                             None, pull_scatter=_pull_scatter, **cfg)
