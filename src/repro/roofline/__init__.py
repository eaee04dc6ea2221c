from .hlo_census import collective_census, CollectiveOp  # noqa: F401
from .analysis import roofline_terms, load_artifacts  # noqa: F401
