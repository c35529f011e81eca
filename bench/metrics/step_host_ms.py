"""Host ms per ``MultiModelEngine.step()`` call: the harness's span around
each call (composition, plan lookup and the executor's enqueue of every
op), total over the window's steps."""


def read(record):
    if not record.get("steps"):
        return None
    return 1e3 * record["step_s"] / record["steps"]
