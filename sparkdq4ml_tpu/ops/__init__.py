from .compiler import bucket_size, clear_cache, is_compilable, run_pipeline
from .expressions import Col, Expr, call_udf, callUDF, col, lit
from .rules import (minimum_price_rule, price_correlation_rule,
                    register_builtin_rules, MIN_PRICE)
from .udf import UDFRegistry, default_registry, register_udf
