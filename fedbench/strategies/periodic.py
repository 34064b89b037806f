"""Strategy ``periodic``, the paper's VPA, as the program builds it.

The configuration's ``strategy`` block: ``{"kind": "periodic", "taus":
{"lo", "hi", "seed"}}``, agent i taking local steps at the first tau_i
updates of each period, tau_i ~ U{lo..hi}.
"""


def program(cfg: dict) -> dict:
    """Keyword arguments of ``repro.core.make_strategy`` beyond ``tau``,
    ``m``, ``backend`` and ``comm``."""
    from repro.core import uniform_taus

    t = cfg["strategy"]["taus"]
    return {"taus": uniform_taus(t["lo"], t["hi"], cfg["agents"],
                                 seed=t["seed"])}
