"""Programs compiled or loaded from the persistent cache inside the window,
from JAX's monitoring events; 0 when set-up warmed every shape.
"""

def read(run):
    return float(run.compiles_in_window)
