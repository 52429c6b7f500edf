"""Process start to window open, compilation included."""


def read(obs, **_):
    return obs.get("setup_s")
