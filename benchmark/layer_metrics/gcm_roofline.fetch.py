"""HBM bound of the payload decrypted in the traced fetches over the device's busy seconds."""
from _shared import gcm_roofline_share as read  # noqa: F401
