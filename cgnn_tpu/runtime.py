"""Process-level runtime settings every entry point shares.

Two decisions live here so that no entry point makes them differently:
which platform a ``--device`` flag binds the process to (and the refusal
when JAX finds another), and where the persistent compilation cache
lives. Importing this module does not import jax.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the one fixed cache directory (gitignored) used when the environment
# does not place the cache: every process of a command, and every later
# command in the same checkout, finds the first one's compiled programs
DEFAULT_COMPILE_CACHE = os.path.join(REPO_ROOT, ".jax_cache")

COMPILE_CACHE_HELP = (
    "persistent XLA compilation cache directory ('' disables). Default: "
    "$JAX_COMPILATION_CACHE_DIR when set — it takes precedence over this "
    "flag — else .jax_cache/ in the checkout"
)


def pin_platform(device: str) -> None:
    """``--device cpu`` binds the process to the CPU backend. Call before
    anything initialises a JAX backend."""
    if device == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"


def device_refusal(device: str) -> str | None:
    """The error to print when ``--device tpu`` was asked for and JAX
    found another platform, else None. Initialises the backend."""
    if device != "tpu":
        return None
    import jax

    platform = jax.devices()[0].platform
    if platform == "tpu":
        return None
    return f"--device=tpu requested but jax found {platform}"


def start(device: str, compile_cache: str | None,
          before_backend=None) -> str | None:
    """What every entry point does before its first JAX call, in the one
    order that works: pin the platform, run ``before_backend`` (train.py's
    ``jax.distributed`` init — it must precede the backend), place the
    compile cache, then initialise the backend and check it against
    ``--device``. Returns the refusal message, or None to go on."""
    pin_platform(device)
    if before_backend is not None:
        before_backend()
    configure_compile_cache(compile_cache)
    return device_refusal(device)


def configure_compile_cache(flag: str | None) -> str | None:
    """Place the persistent compilation cache; returns its directory
    (None when disabled).

    ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself, so nothing is
    set in code and the cache can be placed from outside the program.
    Otherwise ``flag`` (``None`` = :data:`DEFAULT_COMPILE_CACHE`) is used.
    ``flag == ''`` turns the cache off either way.
    """
    import jax

    if flag == "":
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = flag or DEFAULT_COMPILE_CACHE
    jax.config.update("jax_compilation_cache_dir", path)
    return path
