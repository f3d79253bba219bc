from repro.sharding.api import (constrain, use_rules, current_rules,
                                logical_sharding, Rules)

__all__ = ["constrain", "use_rules", "current_rules", "logical_sharding",
           "Rules"]
