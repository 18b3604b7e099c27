"""Host time of ``ServingSession.deploy``: spec, registry round trip and
engine build (layer: spec / registry)."""


def read(run):
    return run.deploy_s
