"""Eigensolver iterations of the traced fit, as the fit reports them
(``fit_result.diagnostics["solver_iterations"]``)."""


def read(ctx):
    fit = ctx.state.get("traced_fit")
    return None if fit is None else float(fit["iterations"])
