"""Device-busy microseconds per delivery round: the union of the
intervals in which an op ran, over the traced window, by its rounds."""


def read(run: dict):
    tr = run.get("trace")
    if not tr or not run.get("rounds"):
        return None
    return 1e6 * tr["busy_s"] / run["rounds"]
