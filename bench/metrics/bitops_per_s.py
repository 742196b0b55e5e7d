"""bitops_per_s: operand bit positions consumed, one XNOR each, counted
from the traffic (n_bits per xnor2 call, M*N*K per binary dot), over the
window from its start to the last completed call."""


def read(r):
    if not r.window.total("bitops"):
        return None
    return r.window.rate("bitops")
