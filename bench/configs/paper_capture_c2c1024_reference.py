"""Plain reference of the paper's capture job: every segment's DFT.

A segment is ``fft_len`` interleaved complex64 samples; its spectrum is
the forward DFT, computed here in float64 by numpy. Nothing of the
program is imported.
"""

import numpy as np


def spectra(re, im):
    """Forward DFT of each row of planar ``(re, im)``, complex128."""
    x = np.asarray(re, np.float64) + 1j * np.asarray(im, np.float64)
    return np.fft.fft(x, axis=-1)
