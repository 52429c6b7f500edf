"""All of one kind of work done in the window over the whole window."""


def read(obs, work: str, **_):
    w = obs["window"]
    n = w["work"].get(work)
    if not n or w["seconds"] <= 0:
        return None
    return n / w["seconds"]
