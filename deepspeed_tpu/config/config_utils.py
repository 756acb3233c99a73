"""Typed config base class.

Capability parity with the reference's ``deepspeed/runtime/config_utils.py``:
a pydantic model base with deprecated-field machinery (old keys keep working,
emit a warning, and auto-populate their replacement), dict-style access
helpers, and scientific-notation-tolerant int parsing.
"""

from __future__ import annotations

from functools import reduce
from typing import Any, Dict

from pydantic import BaseModel, ConfigDict, model_validator

from deepspeed_tpu.utils.logging import logger


class ConfigModel(BaseModel):
    """Base for all typed sub-configs.

    Field deprecation: declare ``json_schema_extra={"deprecated": True,
    "new_param": "other_field", ...}`` on a field. Setting the deprecated field
    warns and (if ``set_new_param``, default True) writes the value through to
    the replacement field, applying ``new_param_fn`` on the way.
    """

    model_config = ConfigDict(
        validate_default=True,
        validate_assignment=True,
        use_enum_values=True,
        populate_by_name=True,
        extra="allow",
        protected_namespaces=(),
        arbitrary_types_allowed=True,
    )

    def __init__(self, strict: bool = False, **data):
        if not strict:  # This is temporary until we refactor all DS configs, allows HF to load models
            data = {k: v for k, v in data.items() if (v != "auto" or k == "replace_method")}
        super().__init__(**data)
        self._deprecated_fields_check()

    def _process_deprecated_field(self, dep_field: str) -> None:
        fields_set = self.model_fields_set
        pydantic_config = self
        kwargs = type(pydantic_config).model_fields[dep_field].json_schema_extra or {}
        new_param_fn = kwargs.get("new_param_fn", lambda x: x)
        param_value = new_param_fn(getattr(pydantic_config, dep_field))
        new_field = kwargs.get("new_param", "")
        dep_msg = kwargs.get("deprecated_msg", "")
        if dep_field in fields_set:
            logger.warning(f"Config parameter {dep_field} is deprecated" +
                           (f" use {new_field} instead" if new_field else "") +
                           (f". {dep_msg}" if dep_msg else ""))
            if new_field and kwargs.get("set_new_param", True):
                if new_field in fields_set:
                    raise ValueError(f"Cannot provide deprecated parameter '{dep_field}' and replacing "
                                     f"parameter '{new_field}' together")
                # A. Get the object with the new param
                # B. Get the explicit keys to traverse (handles nested.fields)
                field_splits = new_field.split(".")
                if len(field_splits) > 1:
                    obj = reduce(getattr, field_splits[:-1], pydantic_config)
                else:
                    obj = pydantic_config
                try:
                    setattr(obj, field_splits[-1], param_value)
                except Exception as e:
                    logger.error(f"Tried setting value for '{new_field}' with value from deprecated "
                                 f"'{dep_field}'")
                    raise e

    def _deprecated_fields_check(self) -> None:
        for field_name, field_info in type(self).model_fields.items():
            extra = field_info.json_schema_extra
            if isinstance(extra, dict) and extra.get("deprecated", False):
                self._process_deprecated_field(field_name)

    # dict-style conveniences used widely in the reference codebase
    def dict(self, **kwargs) -> Dict[str, Any]:
        return self.model_dump(**kwargs)

    def json(self, **kwargs) -> str:
        return self.model_dump_json(**kwargs)

    def get(self, key: str, default: Any = None) -> Any:
        return getattr(self, key, default)

    def __getitem__(self, key: str) -> Any:
        return getattr(self, key)


def get_scalar_param(param_dict: Dict, param_name: str, param_default_value: Any) -> Any:
    return param_dict.get(param_name, param_default_value)


def dict_raise_error_on_duplicate_keys(ordered_pairs):
    """Reject duplicate keys when parsing a JSON config (reference behavior)."""
    d = dict((k, v) for k, v in ordered_pairs)
    if len(d) != len(ordered_pairs):
        counter = {}
        for k, v in ordered_pairs:
            counter[k] = counter.get(k, 0) + 1
        keys = [k for k, v in counter.items() if v > 1]
        raise ValueError(f"Duplicate keys in DeepSpeed config: {keys}")
    return d


class pp_int(int):
    """An int that pretty-prints in scientific notation in config dumps."""

    def __new__(cls, val: int, custom_print_str: str | None = None):
        inst = super().__new__(cls, val)
        inst.custom_print_str = custom_print_str
        return inst

    def __repr__(self):
        if self.custom_print_str:
            return self.custom_print_str
        return f"{self.real:.1e}"


def deep_update(base: dict, override: dict) -> dict:
    """Recursive dict merge returning a new dict (shared by the nested-dict
    config schemas: data_pipeline, compression)."""
    import copy

    out = copy.deepcopy(base)
    for k, v in override.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_update(out[k], v)
        else:
            out[k] = v
    return out
