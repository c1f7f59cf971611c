"""Share of the traced window in which a collective runs on a device and no
other operation does, averaged over the devices."""


def read(run):
    t = run.get("trace")
    if not t or t["devices"] < 2:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
