"""Device bytes of the frame over the wall of ``pd.DataFrame(host)`` + ``execute()``."""


def read(obs):
    return obs["ingest"]["device_bytes"] / 1e9 / obs["ingest"]["wall_s"]
