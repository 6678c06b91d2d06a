"""Host seconds inside ``Net.build`` (``state.py``), children included:
the ``setup.net_build`` spans of the program's own recorder
(``perf/spans.py``) that ended before the window was compiled
(``harness/setup.py``). Its children split it: ``setup.net_build.plan``
(numpy over the index planes) and ``setup.net_build.planes`` (the device
puts). Nothing on a commit without the recorder."""

from benchmark.harness import setup


def read(run: dict):
    return setup.read("setup_net_build_s")
