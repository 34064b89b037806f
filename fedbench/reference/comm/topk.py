"""Reference of payload ``topk``: each row keeps its k largest magnitudes."""
import jax.numpy as jnp


def keep(x, spec: dict):
    """x with all but each row's k largest magnitudes (ties kept) zeroed;
    the threshold is read from a full descending sort of each row."""
    thresh = -jnp.sort(-jnp.abs(x), axis=-1)[..., spec["k"] - 1]
    return jnp.where(jnp.abs(x) >= thresh[..., None], x, 0.0)


def payload_bytes(spec: dict, n: int) -> int:
    """A kept entry is sent as its value and its index, 4 bytes each."""
    return 8 * min(spec["k"], n)
