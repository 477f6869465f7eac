from .kernel import coded_matmul_pallas  # noqa: F401
from .ops import coded_matmul, coded_matmul_code  # noqa: F401
from .ref import coded_matmul_grouped_ref, coded_matmul_ref, lt_encode_ref  # noqa: F401
