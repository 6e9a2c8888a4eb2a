"""The system under test, as the benchmark builds it: a configuration's
generated columns loaded as the engine's tables on the device, its schema
from the configuration's data, and a ``QueryService`` with the options the
configuration states.  The only module of the benchmark that imports the
engine, apart from the run itself."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.service import QueryService
from repro.tables.table import ColumnMeta, ForeignKey, RelSchema, Schema, Table


def schema(spec: dict) -> Schema:
    """The engine's schema from the configuration's ``schema`` and
    ``foreign_keys``.  A column that holds the keys 1..N of a table
    (``keys_of``) has the domain N + 1."""
    rows = spec["rows"]
    relations = {}
    for rel, cols in spec["schema"].items():
        metas = []
        for name, c in cols.items():
            domain = c.get("domain")
            if "keys_of" in c:
                domain = rows[c["keys_of"]] + 1
            metas.append(ColumnMeta(name, unique=bool(c.get("unique")),
                                    domain=domain))
        relations[rel] = RelSchema(rel, tuple(metas))
    fks = tuple(ForeignKey(*fk) for fk in spec["foreign_keys"])
    return Schema(relations=relations, foreign_keys=fks)


def load(spec: dict, data: dict[str, dict]) -> dict[str, Table]:
    """Every table of the configuration on the default device, with the
    dtypes the configuration states.  A table made on the device is
    wrapped where it is, every row live; one made on the host is copied
    over."""
    db = {}
    for rel, cols in spec["schema"].items():
        arrays = {c: data[rel][c] for c in cols}
        if all(isinstance(a, jax.Array) for a in arrays.values()):
            n = next(iter(arrays.values())).shape[0]
            db[rel] = Table({c: a.astype(cols[c]["dtype"])
                             for c, a in arrays.items()},
                            jnp.ones((n,), jnp.int32))
        else:
            db[rel] = Table.from_numpy(
                {c: np.asarray(a, dtype=cols[c]["dtype"])
                 for c, a in arrays.items()})
    return db


def service(spec: dict, db: dict[str, Table], sch: Schema, *,
            profile_annotations: bool = False) -> QueryService:
    return QueryService(db, sch, profile_annotations=profile_annotations,
                        **spec["service"])
