"""Strategy ``consensus``, the paper's CIRL, as the program builds it.

The configuration's ``strategy`` block: ``{"kind": "consensus",
"topology": {"kind": <builder>, ...}, "eps_times_max_degree", "rounds"}``.
The topology is the program's builder of that name in
``repro.core.topology``, called with the fleet size and the block's other
keys; the gossip step is eps = ``eps_times_max_degree`` / Delta, with Delta
the largest closed neighbourhood.
"""


def program(cfg: dict) -> dict:
    """Keyword arguments of ``repro.core.make_strategy`` beyond ``tau``,
    ``m``, ``backend`` and ``comm``."""
    from repro.core import topology

    s = cfg["strategy"]
    spec = dict(s["topology"])
    build = getattr(topology, spec.pop("kind"), None)
    if build is None:
        raise SystemExit(f"fedbench: no topology {s['topology']['kind']!r} "
                         f"in repro.core.topology")
    topo = build(cfg["agents"], **spec)
    return {"topo": topo, "eps": s["eps_times_max_degree"] / topo.max_degree,
            "rounds": s["rounds"]}
