"""Share of the traced serving window in which no operation ran on the
device: 1 - busy / window, busy being the union of the device op
intervals (device trace)."""
from chipbench.readers import idle_share


def read(run):
    return idle_share(run, "serve")
