"""Programs the window compiled inside the measured window: the scan's
``_cache_size()`` after it less before it. Anything but 0 means a shape
was not warmed up."""


def read(run: dict):
    return run.get("window_compiles")
