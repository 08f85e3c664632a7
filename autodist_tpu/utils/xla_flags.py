"""Probe which XLA flags this jaxlib build understands.

XLA hard-aborts the process on any unknown flag in ``XLA_FLAGS``
(``parse_flags_from_env.cc: Unknown flags in XLA_FLAGS``) — there is no
graceful degradation, so anything that adds a build-dependent flag (the
test harness' CPU-collective terminate timeout) must check support first.

A registered flag's name exists as a string literal in the jaxlib shared
objects (``debug_options_flags.cc`` registers them from literals), so a
binary scan answers "is this flag known?" without the alternative — a
subprocess that pays a full backend init just to see whether it aborts.
The scan result is cached on disk keyed by jaxlib version; steady-state
cost is one small JSON read.
"""
import json
import os
import tempfile

_cache = None  # in-process: {flag: bool}


def _cache_path():
    try:
        import jaxlib
        version = getattr(jaxlib, "__version__", "unknown")
    except ImportError:
        version = "nojaxlib"
    return os.path.join(tempfile.gettempdir(),
                        f"autodist_tpu_xla_flags_{version}.json")


def _scan_jaxlib(flag):
    """True when ``flag``'s name appears in any jaxlib shared object."""
    try:
        import jaxlib
    except ImportError:
        return False
    needle = flag.encode()
    root = os.path.dirname(jaxlib.__file__)
    for dirpath, _, files in os.walk(root):
        for fname in files:
            if not fname.endswith(".so"):
                continue
            try:
                with open(os.path.join(dirpath, fname), "rb") as f:
                    import mmap
                    with mmap.mmap(f.fileno(), 0,
                                   access=mmap.ACCESS_READ) as m:
                        if m.find(needle) != -1:
                            return True
            except (OSError, ValueError):  # unreadable / empty file
                continue
    return False


def xla_flag_supported(flag):
    """Whether this jaxlib's XLA recognizes ``flag`` (name, no ``--``)."""
    global _cache
    flag = flag.lstrip("-").split("=")[0]
    if _cache is None:
        _cache = {}
        try:
            with open(_cache_path()) as f:
                _cache = json.load(f)
        except (OSError, ValueError):
            pass
    if flag not in _cache:
        _cache[flag] = _scan_jaxlib(flag)
        try:
            with open(_cache_path(), "w") as f:
                json.dump(_cache, f)
        except OSError:
            pass  # read-only tempdir: in-process cache only
    return _cache[flag]


def collective_timeout_flag(seconds=200):
    """The CPU-collective terminate-timeout flag when this XLA knows it,
    else ``""``.  XLA CPU hard-kills the process (rendezvous.cc) when a
    starved device thread misses a collective by 40s; contended CI hosts
    need headroom, but older builds abort on the very flag that grants
    it."""
    name = "xla_cpu_collective_call_terminate_timeout_seconds"
    if xla_flag_supported(name):
        return f"--{name}={seconds}"
    return ""
