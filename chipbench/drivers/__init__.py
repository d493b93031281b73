"""One driver per KIND of traffic (``train``, ``serve_closed``): the
set-up, the measured window and the comparison with the plain
reference.  A mix's parameters are data; its kind names the driver."""
