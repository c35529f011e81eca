"""Device program executions in the traced window over the requests
completed in it."""


def read(record):
    tr = record.get("trace")
    if not tr or not record.get("traced_completed") or not tr["programs"]:
        return None
    return tr["programs"] / record["traced_completed"]
