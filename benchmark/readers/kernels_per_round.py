"""Op events on the device inside the window's programs per delivery
round, over the traced window (``harness/stages.py``): the launch count.
It repeats exactly between runs of one executable on one seed; where a
conditional of the program takes a branch on some rounds only, it moves
with the seed (0.15 % at ``random-10k-t8.watched``)."""

from benchmark.harness import stages


def read(run: dict):
    red = stages.stage_trace(run)
    if red is None or not run.get("rounds"):
        return None
    return red["ops"] / run["rounds"]
