"""Device-busy time per traced step under the model's ``logits`` scope
(forward and backward), each busy instant counted once by its innermost op."""


def read(w):
    t = w.trace
    v = (t or {}).get("device_by_scope", {}).get("logits")
    if v is None or not t["steps"]:
        return None
    return v / t["steps"] * 1e3
