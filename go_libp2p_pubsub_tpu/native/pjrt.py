"""ctypes bindings for the PJRT C-API bridge (native/pjrt_bridge.cc).

The bridge lets a non-Python host runtime execute the framework's compiled
XLA programs: export a jitted step with `jax.export` (StableHLO), hand the
bytes to the bridge, and run it against host buffers through any PJRT
plugin — the libtpu TPU plugin on real hardware, or a CPU plugin.
The same C ABI is consumable from Go via cgo (survey §2 BUILD-NEW:
"cgo→PJRT bridge").

Typical use:

    exported = jax.export.export(jax.jit(fn))(*example_args)
    plugin = PjrtPlugin.load()                    # finds a plugin .so
    client = plugin.create_client()
    exe = client.compile(exported.mlir_module_serialized)
    outs = exe.run(np_arrays)                     # list of np.ndarray
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

_LIB = None
_LIB_ERR: str | None = None
_ERRLEN = 4096

# PJRT_Buffer_Type enum (pjrt_c_api.h) <-> numpy
_PJRT_DTYPE = {
    np.dtype(np.bool_): 1,    # PRED
    np.dtype(np.int8): 2,
    np.dtype(np.int16): 3,
    np.dtype(np.int32): 4,
    np.dtype(np.int64): 5,
    np.dtype(np.uint8): 6,
    np.dtype(np.uint16): 7,
    np.dtype(np.uint32): 8,
    np.dtype(np.uint64): 9,
    np.dtype(np.float16): 10,
    np.dtype(np.float32): 11,
    np.dtype(np.float64): 12,
}
_NP_DTYPE = {v: k for k, v in _PJRT_DTYPE.items()}


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _lib_path() -> str:
    return os.path.join(_repo_root(), "native", "libpjrt_bridge.so")


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    p, sz, lng, i = ctypes.c_void_p, ctypes.c_size_t, ctypes.c_long, ctypes.c_int
    cp = ctypes.c_char_p
    lib.pjx_load.restype = p
    lib.pjx_load.argtypes = [cp, cp, sz]
    lib.pjx_unload.restype = None
    lib.pjx_unload.argtypes = [p]
    lib.pjx_api_version.restype = None
    lib.pjx_api_version.argtypes = [p, ctypes.POINTER(i), ctypes.POINTER(i)]
    lib.pjx_client_create.restype = p
    lib.pjx_client_create.argtypes = [
        p, ctypes.POINTER(cp), ctypes.POINTER(i),
        ctypes.POINTER(cp), ctypes.POINTER(ctypes.c_int64), sz, cp, sz]
    lib.pjx_client_destroy.restype = None
    lib.pjx_client_destroy.argtypes = [p, p]
    lib.pjx_platform_name.restype = lng
    lib.pjx_platform_name.argtypes = [p, p, cp, sz, cp, sz]
    lib.pjx_device_count.restype = lng
    lib.pjx_device_count.argtypes = [p, p, i, cp, sz]
    lib.pjx_compile.restype = p
    lib.pjx_compile.argtypes = [p, p, cp, sz, cp, cp, sz, cp, sz]
    lib.pjx_executable_destroy.restype = None
    lib.pjx_executable_destroy.argtypes = [p, p]
    lib.pjx_num_outputs.restype = lng
    lib.pjx_num_outputs.argtypes = [p, p, cp, sz]
    lib.pjx_buffer_from_host.restype = p
    lib.pjx_buffer_from_host.argtypes = [
        p, p, p, i, ctypes.POINTER(ctypes.c_int64), sz, cp, sz]
    lib.pjx_buffer_destroy.restype = None
    lib.pjx_buffer_destroy.argtypes = [p, p]
    lib.pjx_buffer_dims.restype = lng
    lib.pjx_buffer_dims.argtypes = [p, p, ctypes.POINTER(ctypes.c_int64), sz, cp, sz]
    lib.pjx_buffer_dtype.restype = lng
    lib.pjx_buffer_dtype.argtypes = [p, p, cp, sz]
    lib.pjx_buffer_to_host.restype = lng
    lib.pjx_buffer_to_host.argtypes = [p, p, p, sz, lng, cp, sz]
    lib.pjx_execute.restype = lng
    lib.pjx_execute.argtypes = [
        p, p, ctypes.POINTER(p), sz, ctypes.POINTER(p), sz, cp, sz]
    return lib


def available() -> bool:
    global _LIB, _LIB_ERR
    if _LIB is not None:
        return True
    if _LIB_ERR is not None:
        return False
    try:
        _LIB = _bind(ctypes.CDLL(_lib_path()))
        return True
    except OSError as e:
        _LIB_ERR = str(e)
        return False


def build() -> bool:
    """Build the bridge (make -C native libpjrt_bridge.so); True on success."""
    global _LIB, _LIB_ERR
    try:
        subprocess.run(
            ["make", "-C", os.path.join(_repo_root(), "native"), "libpjrt_bridge.so"],
            check=True, capture_output=True, timeout=300,
        )
    except (subprocess.SubprocessError, OSError):
        return False
    _LIB_ERR = None
    _LIB = None
    return available()


def default_plugin_path() -> str | None:
    """Plugin search order: an explicit ``PJRT_PLUGIN_PATH``, then the
    installed libtpu (the ``libtpu`` package's shared object, located
    without importing it)."""
    import importlib.util

    explicit = os.environ.get("PJRT_PLUGIN_PATH", "")
    if explicit and os.path.exists(explicit):
        return explicit
    spec = importlib.util.find_spec("libtpu")
    if spec is not None and spec.origin:
        cand = os.path.join(os.path.dirname(spec.origin), "libtpu.so")
        if os.path.exists(cand):
            return cand
    return None


class PjrtError(RuntimeError):
    pass


def _err_buf():
    return ctypes.create_string_buffer(_ERRLEN)


def default_compile_options() -> bytes:
    """Serialized single-device xla CompileOptionsProto (via jaxlib)."""
    from jaxlib import xla_client

    return xla_client.CompileOptions().SerializeAsString()


class PjrtBuffer:
    def __init__(self, client: "PjrtClient", handle):
        self._c = client
        self._h = handle
        client._track(self)

    def _invalidate(self):
        """Drop the handle without destroying it — the owning client is
        being destroyed and takes its buffers with it."""
        self._h = None

    def __del__(self):
        try:
            if self._h and _LIB is not None:
                _LIB.pjx_buffer_destroy(self._c._p._h, self._h)
                self._c._untrack(self)
        except Exception:
            pass
        self._h = None

    def to_numpy(self) -> np.ndarray:
        lib, b, err = _LIB, self._c._p._h, _err_buf()
        dt = lib.pjx_buffer_dtype(b, self._h, err, _ERRLEN)
        if dt < 0:
            raise PjrtError(err.value.decode())
        dims = (ctypes.c_int64 * 16)()
        nd = lib.pjx_buffer_dims(b, self._h, dims, 16, err, _ERRLEN)
        if nd < 0:
            raise PjrtError(err.value.decode())
        shape = tuple(dims[i] for i in range(nd))
        npdt = _NP_DTYPE[dt]
        out = np.empty(shape, dtype=npdt)
        n = lib.pjx_buffer_to_host(
            b, self._h, out.ctypes.data_as(ctypes.c_void_p),
            out.nbytes, out.itemsize, err, _ERRLEN)
        if n < 0:
            raise PjrtError(err.value.decode())
        return out


class PjrtExecutable:
    def __init__(self, client: "PjrtClient", handle):
        self._c = client
        self._h = handle
        client._track(self)

    def _invalidate(self):
        self._h = None

    def __del__(self):
        try:
            if self._h and _LIB is not None:
                _LIB.pjx_executable_destroy(self._c._p._h, self._h)
                self._c._untrack(self)
        except Exception:
            pass
        self._h = None

    @property
    def num_outputs(self) -> int:
        err = _err_buf()
        n = _LIB.pjx_num_outputs(self._c._p._h, self._h, err, _ERRLEN)
        if n < 0:
            raise PjrtError(err.value.decode())
        return n

    def run(self, inputs) -> list[np.ndarray]:
        """Execute with host arrays (or PjrtBuffers); returns host arrays.

        When built via compile_exported, arguments the compiler pruned
        are dropped here (pass the original full argument list)."""
        kept = getattr(self, "_kept_var_idx", None)
        if kept is not None:
            inputs = [inputs[i] for i in kept]
        bufs = [
            x if isinstance(x, PjrtBuffer) else self._c.buffer_from_numpy(np.asarray(x))
            for x in inputs
        ]
        lib, err = _LIB, _err_buf()
        argv = (ctypes.c_void_p * len(bufs))(*[b._h for b in bufs])
        cap = max(self.num_outputs, 1)
        outv = (ctypes.c_void_p * cap)()
        n = lib.pjx_execute(
            self._c._p._h, self._h, argv, len(bufs), outv, cap, err, _ERRLEN)
        if n < 0:
            raise PjrtError(err.value.decode())
        outs = []
        for i in range(n):
            ob = PjrtBuffer(self._c, outv[i])
            outs.append(ob.to_numpy())
        return outs


class PjrtClient:
    def __init__(self, plugin: "PjrtPlugin", handle):
        self._p = plugin
        self._h = handle
        # children (buffers/executables) die with the client: destroying
        # the PJRT client invalidates them plugin-side, so their __del__
        # must not call into the API afterwards (use-after-free)
        import weakref

        self._children = weakref.WeakSet()

    def _track(self, child):
        self._children.add(child)

    def _untrack(self, child):
        self._children.discard(child)

    def close(self):
        if self._h and _LIB is not None:
            for child in list(self._children):
                child._invalidate()
            self._children.clear()
            _LIB.pjx_client_destroy(self._p._h, self._h)
            self._h = None

    @property
    def platform_name(self) -> str:
        buf, err = ctypes.create_string_buffer(256), _err_buf()
        n = _LIB.pjx_platform_name(self._p._h, self._h, buf, 256, err, _ERRLEN)
        if n < 0:
            raise PjrtError(err.value.decode())
        return buf.value.decode()

    def device_count(self, addressable: bool = True) -> int:
        err = _err_buf()
        n = _LIB.pjx_device_count(
            self._p._h, self._h, 1 if addressable else 0, err, _ERRLEN)
        if n < 0:
            raise PjrtError(err.value.decode())
        return n

    def compile(self, code: bytes | str, fmt: str = "mlir",
                options: bytes | None = None) -> PjrtExecutable:
        if isinstance(code, str):
            code = code.encode()
        if options is None:
            options = default_compile_options()
        err = _err_buf()
        h = _LIB.pjx_compile(
            self._p._h, self._h, code, len(code), fmt.encode(),
            options, len(options), err, _ERRLEN)
        if not h:
            raise PjrtError(err.value.decode())
        return PjrtExecutable(self, h)

    def compile_exported(self, exported) -> "PjrtExecutable":
        """Compile a `jax.export.Exported`, recording its kept-argument
        indices on the executable. XLA prunes unused parameters from the
        compiled program, so executing with the caller's full argument
        list mismatches the executable's arity (observed to crash the
        remote backend); `Exported.module_kept_var_idx` says which of the
        original arguments survive, and run() applies it."""
        exe = self.compile(exported.mlir_module_serialized)
        exe._kept_var_idx = tuple(exported.module_kept_var_idx)
        return exe

    def buffer_from_numpy(self, arr: np.ndarray) -> PjrtBuffer:
        arr = np.ascontiguousarray(arr)
        if arr.dtype not in _PJRT_DTYPE:
            raise PjrtError(f"unsupported dtype {arr.dtype}")
        dims = (ctypes.c_int64 * max(arr.ndim, 1))(*arr.shape)
        err = _err_buf()
        h = _LIB.pjx_buffer_from_host(
            self._p._h, self._h, arr.ctypes.data_as(ctypes.c_void_p),
            _PJRT_DTYPE[arr.dtype], dims, arr.ndim, err, _ERRLEN)
        if not h:
            raise PjrtError(err.value.decode())
        return PjrtBuffer(self, h)


class PjrtPlugin:
    def __init__(self, handle, path: str):
        self._h = handle
        self.path = path

    @classmethod
    def load(cls, path: str | None = None) -> "PjrtPlugin":
        if not available() and not build():
            raise PjrtError(f"bridge library unavailable: {_LIB_ERR}")
        path = path or default_plugin_path()
        if path is None:
            raise PjrtError("no PJRT plugin found (set PJRT_PLUGIN_PATH)")
        err = _err_buf()
        h = _LIB.pjx_load(path.encode(), err, _ERRLEN)
        if not h:
            raise PjrtError(err.value.decode())
        return cls(h, path)

    @property
    def api_version(self) -> tuple[int, int]:
        major, minor = ctypes.c_int(), ctypes.c_int()
        _LIB.pjx_api_version(self._h, ctypes.byref(major), ctypes.byref(minor))
        return major.value, minor.value

    def create_client(self, options: dict | None = None) -> PjrtClient:
        """Create a client. `options` are plugin-specific NamedValues:
        str -> kString, bool -> kBool, int -> kInt64."""
        options = options or {}
        n = len(options)
        names = (ctypes.c_char_p * max(n, 1))()
        types = (ctypes.c_int * max(n, 1))()
        svals = (ctypes.c_char_p * max(n, 1))()
        ivals = (ctypes.c_int64 * max(n, 1))()
        for idx, (k, v) in enumerate(options.items()):
            names[idx] = k.encode()
            if isinstance(v, str):
                types[idx], svals[idx] = 0, v.encode()
            elif isinstance(v, bool):
                types[idx], ivals[idx] = 2, int(v)
            elif isinstance(v, int):
                types[idx], ivals[idx] = 1, v
            else:
                raise PjrtError(f"unsupported option type for {k}: {type(v)}")
        err = _err_buf()
        h = _LIB.pjx_client_create(
            self._h, names, types, svals, ivals, n, err, _ERRLEN)
        if not h:
            raise PjrtError(err.value.decode())
        return PjrtClient(self, h)
