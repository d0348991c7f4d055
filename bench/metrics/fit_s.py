"""fit_s: time to the frequent set, the measured window's wall time over
the fits completed in it (host clock)."""


def read(x):
    return x.window_s / x.fits
