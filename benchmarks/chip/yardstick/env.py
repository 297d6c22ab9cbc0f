"""The environment a benchmark process sets before anything imports JAX."""
import os


def prepare() -> None:
    """JAX's compile cache at the program's fixed path inside the checkout
    (unless ``JAX_COMPILATION_CACHE_DIR`` names one), every program cached
    however fast it compiled, and no compiler logs under /tmp."""
    from repro.launch.compile_cache import CACHE_DIR
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
