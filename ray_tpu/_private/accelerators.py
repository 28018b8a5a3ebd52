"""Who may open the TPU backend, and where compiled programs are kept.

One rule: a worker process may open the TPU backend if and only if the
task or actor it was started for holds whole ``TPU`` slots. Every other
worker is pinned to the CPU; node processes and the driver never open a
backend at all. The environment of a worker is fixed when it is spawned,
so the rule is a pure function of (the environment the node was started
with, the grant, whether the chips were detected or declared) —
``worker_env`` — and the worker pool is keyed by the grant.

Nothing here imports jax: counting chips or choosing an environment must
not initialise a backend in the process that does it.
"""

from __future__ import annotations

import glob
import os
import sys
from typing import Dict, Mapping, Optional, Sequence

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def detect_tpus() -> int:
    """Count this host's TPU chips from ``/dev`` (reference analogue:
    ``_private/accelerators/tpu.py``): ``/dev/accel*`` on a TPU VM with
    the accel driver, numbered ``/dev/vfio/N`` groups under vfio."""
    accel = glob.glob("/dev/accel[0-9]*")
    if accel:
        return len(accel)
    return sum(1 for p in glob.glob("/dev/vfio/*")
               if os.path.basename(p).isdigit())


def grant_error(n_granted: int, host_chips: int,
                detected: bool) -> Optional[str]:
    """Why a whole-slot grant cannot be given a process of its own, or
    None. On detected chips a process drives one chip or the whole host;
    anything between would need a sub-topology two processes could
    collide on. Declared chips (``num_tpus=`` / ``resources=``) are the
    caller's own account of the host and are never refused."""
    if detected and 1 < n_granted < host_chips:
        return (f"a TPU grant is one chip or the whole host: asked for "
                f"{n_granted} of this host's {host_chips} chips")
    return None


def worker_env(parent_env: Mapping[str, str],
               grant: Optional[Sequence[int]],
               host_chips: int,
               detected: bool) -> Dict[str, Optional[str]]:
    """Environment changes for a worker spawned with ``grant`` (the TPU
    slot ids its task or actor holds, or None): name -> value, None
    meaning unset.

    No grant: pinned to the CPU. A grant: the platform the node was
    started with (so a parent that names ``cpu`` keeps everything on the
    CPU); when it names none and the chips were detected, ``tpu`` is
    named so that JAX raises instead of quietly falling back. One chip
    of a larger host restricts the process to that chip; the whole host
    leaves visibility alone. The compile cache goes where
    ``compile_cache_dir`` says."""
    if not grant:
        return {"JAX_PLATFORMS": "cpu"}
    out: Dict[str, Optional[str]] = {
        "JAX_PLATFORMS": (parent_env.get("JAX_PLATFORMS")
                          or ("tpu" if detected else None)),
        _CACHE_ENV: compile_cache_dir(parent_env),
    }
    if len(grant) == 1 and host_chips > 1:
        out["TPU_VISIBLE_CHIPS"] = str(grant[0])
        # a one-chip process topology, under both of the names libtpu
        # reads (a TPU VM's environment presets the HOST pair to the
        # whole host's bounds)
        for name in ("TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS",
                     "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS"):
            out[name] = "1,1,1"
    return out


def pool_key(env_key: str, grant: Optional[Sequence[int]]) -> str:
    """Worker-pool key: the runtime-env key, plus the slot ids for a
    worker that may open the backend. A granted task never lands on a
    CPU-pinned pooled worker, nor a CPU task on a process that holds a
    chip, nor one grant on the process of another."""
    if not grant:
        return env_key
    return f"{env_key}|tpu:{','.join(map(str, sorted(grant)))}"


def compile_cache_dir(env: Mapping[str, str] = os.environ) -> str:
    """Where JAX's persistent compile cache lives: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else one fixed directory beside
    the package — the path is part of the cache's key, so it is never
    derived from a temp dir, the session, a pid or a clock."""
    return env.get(_CACHE_ENV) or os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Call at the start of a process that will open the backend, before
    its first compile. With the variable set JAX uses it unaided and no
    directory is set in code; unset, the fixed directory is exported (so
    children inherit it) and handed to an already imported jax."""
    path = compile_cache_dir()
    if not os.environ.get(_CACHE_ENV):
        os.environ[_CACHE_ENV] = path
        if "jax" in sys.modules:
            sys.modules["jax"].config.update("jax_compilation_cache_dir",
                                             path)
    return path


def jax_backend_initialized() -> bool:
    """True once this process has opened a JAX backend. Never opens one,
    never imports jax, and never queues behind jax's backend lock, which
    is held for as long as a backend takes to open (the TPU runtime: 8 to
    16 s): a backend that another thread is opening this instant is not
    open yet. The table is read under that lock, so a True means every
    backend is up and `jax.local_devices()` will not block."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    if not xla_bridge._backend_lock.acquire(blocking=False):
        return False
    try:
        return bool(xla_bridge._backends)
    finally:
        xla_bridge._backend_lock.release()
