"""Kernel nodes of the plans the engine resolved for its rounds (window
and drain) over the requests those rounds served: the plans'
fingerprint, on the host."""


def read(record):
    if not record.get("served") or not record.get("plan_kernels"):
        return None
    return record["plan_kernels"] / record["served"]
