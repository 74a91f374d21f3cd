from . import flags  # noqa: F401
from .flags import get_flags, set_flags  # noqa: F401
from . import concurrency  # noqa: F401
from . import resilience  # noqa: F401

# supervised workers (launch --supervise exports PADDLE_SUPERVISE_STORE
# into the gang's env) get the SIGUSR1 thread-dump handler at IMPORT:
# the watchdog signals the gang before killing it, and SIGUSR1's
# default disposition would otherwise terminate — dumpless — any
# worker that wedged before Model.fit installed the handler itself
import os as _os
if _os.environ.get("PADDLE_SUPERVISE_STORE"):
    concurrency.install_signal_dump()
    # the flight recorder's crash excepthook installs on its import
    # (profiler/flight.py checks the same env) — import it NOW so a
    # worker that dies before any subsystem touches the recorder still
    # leaves its event history next to the thread dump
    from ..profiler import flight as _flight  # noqa: F401
from . import chaos  # noqa: F401
from . import compile_cache  # noqa: F401
from . import artifact_store  # noqa: F401
from . import cpp_extension  # noqa: F401

# place JAX's persistent compilation cache before anything compiles
# (JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache); the AOT artifact
# store (artifact_store.py) arms off FLAGS_compile_cache_dir at its own
# import.
compile_cache.configure()
