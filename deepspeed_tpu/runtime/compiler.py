"""torch.compile API shim.

Reference: ``runtime/compiler.py`` + ``engine.py:3665 compile()`` — opt-in
graph compilation of the wrapped module. Under this framework everything is
ALREADY traced and XLA-compiled at first dispatch (the engine jits
fwd_bwd/apply as whole programs), so ``compile()`` only records the request —
but ``is_compiled`` keeps the reference's contract: False until ``compile()``
has been called, True afterwards."""

import os
from typing import Any, Callable, Optional

from ..utils.logging import logger


def is_compile_supported() -> bool:
    return True


def _reset_cache_latch() -> None:
    """jax's compilation-cache module latches a "disabled" state at the
    first compile that runs with no cache dir configured (model.init, eager
    ops before engine construction all count). After that latch, config
    updates are silently ignored — entries log "cache is disabled/not
    initialized". reset_cache() clears the latch so the NEXT compile
    re-initializes against the directory just configured."""
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """Where compiled programs persist: ``$JAX_COMPILATION_CACHE_DIR`` when
    the launcher set it, else ``<checkout>/.jax_cache`` (git-ignored). The
    path is part of the cache's key, so it is never a home, temporary, pid-
    or time-derived directory: a cache that moves never hits."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def configure_compile_cache(compile_config=None) -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    — the one decision for trainer, server and ``chip_smoke.py`` alike.
    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and
    nothing here sets another directory. Returns the directory in use.

    Also installs the process-wide XLA backend-compile listener
    (``ds_xla_backend_compile_seconds``): this is the one place every
    engine passes through before its first compile, so compiles that bypass
    the per-key ``CompileWatch`` wrappers (model init, eager ops) are still
    visible. Idempotent."""
    import jax
    from ..observability.xla import install_backend_compile_listener
    install_backend_compile_listener()
    path = compile_cache_dir()
    min_secs = getattr(compile_config, "cache_min_compile_secs", None)
    if min_secs is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_secs))
    if jax.config.jax_compilation_cache_dir != path:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        _reset_cache_latch()
    return path


def disable(fn: Callable) -> Callable:
    """Reference compiler.disable decorator — marks a function to stay out
    of graph capture. JAX equivalent: the function simply isn't jitted; for
    callers inside jit the right tool is jax.pure_callback, which this shim
    cannot insert automatically — so it returns the fn unchanged."""
    return fn


class CompiledModuleWrapper:

    def __init__(self, module, compile_config=None):
        self.module = module
        self._is_compiled = False

    def compile(self, *a, **kw):
        self._is_compiled = True
        return self.module

    @property
    def is_compiled(self) -> bool:
        return self._is_compiled


def attach_compile_api(engine) -> None:
    """Give an engine the reference's compile()/is_compiled surface
    (reference engine.py:3665: is_compiled is False until compile() runs)."""
    engine.is_compiled = False

    def compile(backend: Optional[str] = None, compile_kwargs: Optional[dict] = None,
                schedule: Any = None) -> None:
        logger.info("compile(): engine programs are XLA-compiled by construction; "
                    f"request recorded (backend={backend})")
        engine.is_compiled = True

    engine.compile = compile
