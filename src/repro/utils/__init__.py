from repro.utils import compile_cache, pytree  # noqa: F401
