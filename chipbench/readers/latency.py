"""One member of the latency family the serving driver reduced from the
client's stamps."""


def read(run, name):
    family = getattr(run, 'family_ms', None)
    if family is None:
        return None
    return family.get(name)
