"""Reference of optimizer ``sgd``: theta <- theta - eta * d."""


def init(flat, spec: dict) -> dict:
    return {}


def step(flat, d, state: dict, eta: float, spec: dict):
    return flat - eta * d, state


def sync(state: dict) -> dict:
    return state
