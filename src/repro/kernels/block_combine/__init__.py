from .kernel import block_combine, tiles  # noqa: F401
