"""A number the runner counted over the window (``key`` of its facts),
optionally per second of window."""


def read(params, facts):
    w = facts["window"]
    if params["key"] not in w or w[params["key"]] is None:
        return None
    v = float(w[params["key"]])
    return v / w["seconds"] if params.get("per_second") else v
