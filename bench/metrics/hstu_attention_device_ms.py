"""Device-busy time per traced step under the ``hstu_attention`` scope:
HSTU's attention kernels forward and backward and the bias tiles built for
them, each busy instant counted once by its innermost op."""


def read(w):
    t = w.trace
    v = (t or {}).get("device_by_scope", {}).get("hstu_attention")
    if v is None or not t["steps"]:
        return None
    return v / t["steps"] * 1e3
