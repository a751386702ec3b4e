"""Distribution substrate: logical-axis sharding rules, collectives helpers,
fault tolerance."""
from .sharding import (Rules, current_rules, logical_constraint, make_rules,
                       put_db_sharded, resolve_spec, serve_mesh,
                       stack_shards, tree_shardings, use_rules)
