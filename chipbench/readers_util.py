"""Shared by the readers: dotted paths into a status document."""


def get(doc, path: str):
    for key in path.split("."):
        if not isinstance(doc, dict) or key not in doc:
            return None
        doc = doc[key]
    return doc


def delta(pair: dict, paths: list):
    """Sum over `paths` of after - before; None where one is missing."""
    total = 0.0
    for p in paths:
        a, b = get(pair["after"], p), get(pair["before"], p)
        if a is None or b is None:
            return None
        total += a - b
    return total
