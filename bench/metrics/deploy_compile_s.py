"""Host seconds of ``DeploymentSession.compile`` + ``precompile`` in the
run that built the cached artifact (stored with it)."""


def read(record):
    return record.get("deploy_compile_s")
