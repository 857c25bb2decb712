"""Mean milliseconds per re-advised window that no layer of the program's
report covers: the harness's per-window re-advice time minus the sum of
that window's seven ``host_ms`` layers.  With the seven layer means it
adds up to the mean re-advice."""
from window_layers import unspanned_ms


def read(run):
    return unspanned_ms(run)
