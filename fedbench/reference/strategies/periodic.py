"""Reference of strategy ``periodic`` (VPA): agent i's gradient counts at
the first tau_i local updates of each period and is masked out after."""
import jax.numpy as jnp
import numpy as np


def uniform_taus(spec: dict, m: int) -> np.ndarray:
    """tau_i ~ U{lo..hi} from numpy's default_rng(seed), agent 0 pinned to
    hi, sorted descending (the paper's "tau = a~b")."""
    rng = np.random.default_rng(spec["seed"])
    taus = rng.integers(spec["lo"], spec["hi"] + 1, size=m)
    taus[0] = spec["hi"]
    return np.sort(taus)[::-1].copy()


class Strategy:
    def __init__(self, cfg: dict):
        taus = uniform_taus(cfg["strategy"]["taus"], cfg["agents"])
        self.mask = jnp.asarray(
            (taus[:, None] > np.arange(cfg["tau"])[None, :]).astype(np.float32))

    def init(self, flat) -> dict:
        return {}

    def direction(self, g, state: dict, offset):
        """The step each agent's optimizer takes at update ``offset`` of a
        period, and the strategy's state after it."""
        return self.mask[:, offset][:, None] * g, state

    def receives_per_period(self) -> int:
        """Payloads received by agents from one another within a period."""
        return 0
