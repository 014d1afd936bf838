"""HBM bound of the payload encrypted in the traced copy over the device's busy seconds in it."""
from _shared import gcm_roofline_share as read  # noqa: F401
