"""Host seconds inside ``GossipSubState.init`` (``models/gossipsub.py``),
summed over the ``setup.state_init`` spans that ended before the window
was compiled (``harness/setup.py``): the host time of the call, the eager
programs it compiles or loads included; what the device still runs when
the call returns is in the harness's ``fresh_state``. Nothing on a commit
without the recorder."""

from benchmark.harness import setup


def read(run: dict):
    return setup.read("setup_state_init_s")
