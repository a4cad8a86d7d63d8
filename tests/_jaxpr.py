"""Walk a jaxpr, nested jaxprs included, for the structural tests."""


def eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for x in vals:
                if hasattr(x, "jaxpr"):        # ClosedJaxpr
                    yield from eqns(x.jaxpr)
                elif hasattr(x, "eqns"):       # Jaxpr
                    yield from eqns(x)
