"""Device, serving cells: 100 * (1 - busy / window) over the traced
window, busy being the union of device-operation intervals."""


def read(view):
    tr = getattr(view, "trace", None)
    if not tr or not tr.get("host_window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["host_window_s"])
