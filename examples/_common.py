"""Shared CLI helpers for the example scripts.

Every example runs as a standalone file, so ``import _common`` resolves
through the script's own directory on sys.path.
"""


def add_device_flag(ap):
    ap.add_argument("--cpu", action="store_true",
                    help="run on the host cpu backend")
    return ap


def apply_device_flag(args):
    if getattr(args, "cpu", False):
        import jax
        jax.config.update("jax_platforms", "cpu")
