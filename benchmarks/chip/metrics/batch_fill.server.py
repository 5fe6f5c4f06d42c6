"""Share of the batch slots that ``ModelServer`` filled with requests in the
window: completed / (batches x batch_slots), from its own counters."""


def read(ctx):
    s = ctx["run"].get("server")
    if not s or not s["batches"]:
        return None
    return 100.0 * s["completed"] / (s["batches"] * s["batch_slots"])
