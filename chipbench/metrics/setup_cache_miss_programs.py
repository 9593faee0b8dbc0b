"""Programs the backend compiled before the program was ready because jax's
persistent cache did not hold them (``backend_compile`` spans with ``cache``
``miss``); left out where the cache is not armed."""


def read(facts):
    from chipbench.lib import startup

    return startup.cache_misses()
