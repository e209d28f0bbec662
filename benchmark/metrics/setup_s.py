"""Everything from the start of the process to the first request of the
window: imports, data, ingest, the first pass with its compiles or cache loads."""


def read(obs):
    return obs["setup_s"]
