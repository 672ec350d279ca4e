"""Device: XLA compilations (persistent-cache loads included) during the
window, from JAX's monitoring events; 0 when set-up warmed every shape
(moves ``fit_s``)."""


def read(run):
    return run.compiles_in_window
