"""Rule compilation: specs -> runnable rules with precompiled expressions.

Mirrors /root/reference/pkg/rules/rules.go Compile (rules.go:716-897): every
template field becomes a compiled expression at boot (literals wrapped as
literal expressions), tupleSets compile to expressions producing lists of
relationship strings, and `if` conditions compile to boolean programs.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from itertools import repeat
from typing import Callable, Iterator, Optional, Sequence

from ..models.tuples import TupleError, parse_rel_fields
from .expr import CompiledExpr, ExprError, compile_expr, compile_template
from .input import ResolveInput
from .proxyrule import (
    PreFilterSpec,
    PostFilterSpec,
    RuleConfig,
    StringOrTemplate,
    UpdateSpec,
)


class CompileError(ValueError):
    pass


@dataclass(frozen=True)
class ResolvedRel:
    resource_type: str
    resource_id: str
    resource_relation: str
    subject_type: str
    subject_id: str
    subject_relation: str = ""

    def __str__(self) -> str:
        s = (f"{self.resource_type}:{self.resource_id}"
             f"#{self.resource_relation}"
             f"@{self.subject_type}:{self.subject_id}")
        if self.subject_relation:
            s += f"#{self.subject_relation}"
        return s


# the template-data roots that differ from one object of a listed answer to
# the next (authz/postfilter.py rewrites them per object); every other root
# (headers, request, user, body) is the request's own. ``this`` is the whole
# map, so a read of it counts as a read of ``object``.
ITEM_ROOTS = frozenset({"name", "namespace", "namespacedName", "resourceId",
                        "object", "metadata", "this"})


# the item roots a list can hand a rule as columns: an object's namespace,
# its name, and their join (``{ns}/{name}``, the name alone where there is
# no namespace) under its two names
COLUMN_ROOTS = frozenset({"namespace", "name", "namespacedName",
                          "resourceId"})


_REL_FIELDS = ("resource_type", "resource_id", "resource_relation",
               "subject_type", "subject_id", "subject_relation")
# a relationship's six fields, in ResolvedRel's order
RelFields = tuple[str, str, str, str, str, str]


def _column_root(expr: CompiledExpr) -> Optional[str]:
    """The item root that ``expr`` is, bare (``{{namespace}}``,
    ``{{ name }}``: whitespace is insignificant); None where it is
    anything else."""
    src = "".join(expr.source.split())
    return src if src in COLUMN_ROOTS and src in expr.refs else None


def _field(data: dict, name: str, expr: Optional[CompiledExpr]) -> str:
    """One relationship field evaluated against ``data``."""
    if expr is None:  # no subject relation
        return ""
    try:
        v = expr.evaluate_str(data)
    except ExprError as e:
        raise ExprError(f"resolving relationship: {e}") from None
    if not v and name != "subject_relation":
        raise ExprError(f"relationship field {name} resolved empty")
    return v


class RelationshipExpr:
    """A compiled expression producing relationships from a ResolveInput
    (reference RelationshipExpr interface, rules.go:148-152)."""

    @property
    def refs(self) -> frozenset:
        """Root data-map identifiers the expression may read: the union
        of its compiled expressions' (``CompiledExpr.refs``)."""
        raise NotImplementedError

    def generate(self, input: ResolveInput) -> list[ResolvedRel]:
        raise NotImplementedError

    def per_list(self, data: dict) -> Callable[[], list[RelFields]]:
        """A resolver over ONE template-data map whose ``ITEM_ROOTS``
        entries the caller rewrites between calls (the objects of one
        list): what reads none of them is evaluated here, once; a call
        evaluates the rest against ``data`` as it then stands and
        returns the fields of what ``generate`` would for that input."""
        raise NotImplementedError

    @property
    def columns(self) -> Optional[dict[int, str]]:
        """Where the expression reads the item roots only as whole fields
        that are bare ``COLUMN_ROOTS``: the place of each such field ->
        the root it is (``RelExpr.resolve_columns``); None otherwise."""
        return None


@dataclass
class RelExpr(RelationshipExpr):
    """Six compiled field expressions -> exactly one relationship
    (reference RelExpr, rules.go:204-210)."""

    resource_type: CompiledExpr
    resource_id: CompiledExpr
    resource_relation: CompiledExpr
    subject_type: CompiledExpr
    subject_id: CompiledExpr
    subject_relation: Optional[CompiledExpr] = None

    @property
    def refs(self) -> frozenset:
        exprs = [getattr(self, f_) for f_ in _REL_FIELDS]
        return frozenset().union(*(e.refs for e in exprs if e is not None))

    def generate(self, input: ResolveInput) -> list[ResolvedRel]:
        return [ResolvedRel(*f)
                for f in self.per_list(input.template_data())()]

    def per_list(self, data: dict) -> Callable[[], list[RelFields]]:
        fields = [(f_, getattr(self, f_)) for f_ in _REL_FIELDS]
        per_item = [i for i, (_, e) in enumerate(fields)
                    if e is not None and e.refs & ITEM_ROOTS]
        values = [None if i in per_item else _field(data, *f)
                  for i, f in enumerate(fields)]

        def resolve() -> list[RelFields]:
            for i in per_item:
                values[i] = _field(data, *fields[i])
            return [tuple(values)]

        return resolve

    @property
    def columns(self) -> Optional[dict[int, str]]:
        """Derived from the field expressions, as ``PreFilter.
        mapping_kind`` is: a field that reads an item root is a column
        where it is a bare one (``{{namespace}}``, ``{{ name }}``), and
        not where it reads one inside an expression
        (``{{split_name(resourceId)}}``, ``{{object.metadata.name}}``)."""
        places = {}
        for i, f_ in enumerate(_REL_FIELDS):
            expr = getattr(self, f_)
            if expr is not None and expr.refs & ITEM_ROOTS:
                places[i] = _column_root(expr)
                if places[i] is None:
                    return None
        return places

    def resolve_columns(self, data: dict, roots: dict[str, Sequence[str]],
                        n: int) -> Iterator[RelFields]:
        """Where ``columns`` is not None: ``per_list(data)`` for n objects
        whose item roots come as columns of strings (``roots``: each root
        that ``columns`` names -> its value for every object, in order)
        -> the fields each object's call would return, in order, zipped
        from the columns with no template evaluated; what reads no item
        root is evaluated once, as ``per_list`` does. Raises what the
        first failing call would: a field but ``subject_relation`` that is
        empty for some object."""
        places = self.columns
        values = [None if i in places else
                  _field(data, f_, getattr(self, f_))
                  for i, f_ in enumerate(_REL_FIELDS)]
        empty = [(roots[root].index(""), i) for i, root in places.items()
                 if _REL_FIELDS[i] != "subject_relation"
                 and "" in roots[root]]
        if empty:
            raise ExprError(f"relationship field {_REL_FIELDS[min(empty)[1]]}"
                            " resolved empty")
        return zip(*[roots[places[i]] if i in places else repeat(v, n)
                     for i, v in enumerate(values)])


@dataclass
class TupleSetExpr(RelationshipExpr):
    """One compiled expression -> a list of relationship strings, each
    parsed into a relationship (reference TupleSetExpr, rules.go:154-201)."""

    expr: CompiledExpr

    @property
    def refs(self) -> frozenset:
        return self.expr.refs

    def generate(self, input: ResolveInput) -> list[ResolvedRel]:
        return [ResolvedRel(*f) for f in self._resolve(input.template_data())]

    def per_list(self, data: dict) -> Callable[[], list[RelFields]]:
        if self.refs & ITEM_ROOTS:
            return lambda: self._resolve(data)
        rels = self._resolve(data)
        return lambda: rels

    def _resolve(self, data: dict) -> list[RelFields]:
        v = self.expr.evaluate(data)
        if not isinstance(v, list):
            raise ExprError(
                f"tupleSet expression must evaluate to a list of relationship "
                f"strings, got {type(v).__name__}")
        out: list[RelFields] = []
        for i, item in enumerate(v):
            if not isinstance(item, str):
                raise ExprError(f"tupleSet item {i} is not a string")
            try:
                f_ = parse_rel_fields(item)
            except TupleError as e:
                raise ExprError(f"tupleSet item {i}: {e}") from None
            out.append((
                f_["resource_type"], f_["resource_id"], f_["relation"],
                f_["subject_type"], f_["subject_id"],
                f_["subject_relation"] or "",
            ))
        return out


@dataclass
class PreFilter:
    """LookupResources-based pre-filter (reference rules.go:686-699): the
    rel's resource_id must resolve to `$`; name/namespace expressions map
    each looked-up object id to an allowed (namespace, name)."""

    name_expr: CompiledExpr
    namespace_expr: Optional[CompiledExpr]
    rel: RelExpr

    @property
    def mapping_kind(self) -> str:
        """Classification of the id->(ns, name) mapping so the hot
        prefilter loop can vectorize the dominant forms: "identity"
        ({{resourceId}} name, no namespace expr), "split"
        (split_name/split_namespace pair), or "general" (anything else,
        incl. braceless literals — those have empty refs and mean a
        CONSTANT name, never the id). A property derived from the exprs
        (not stored state) so tests substituting duck-typed expr fakes
        can never leave a stale classification; whitespace inside the
        expression is insignificant ('{{ split_name( resourceId ) }}'
        still vectorizes)."""
        def norm(e) -> Optional[str]:
            if e is None or "resourceId" not in getattr(e, "refs", ()):
                return None
            return "".join(getattr(e, "source", "").split())

        name_src = norm(self.name_expr)
        if name_src == "resourceId" and self.namespace_expr is None:
            return "identity"
        if name_src == "split_name(resourceId)" and \
                norm(self.namespace_expr) == "split_namespace(resourceId)":
            return "split"
        return "general"

    def mapping_shareable(self) -> bool:
        """True when the id→(namespace, name) mapping depends on nothing
        but the looked-up resourceId — then two watchers resolving the
        SAME relationship produce identical allowed sets, and the watch
        hub may compute once and fan out (exprs referencing user/headers/
        request fields disable sharing; over-collected refs only cost the
        optimization, never correctness)."""
        refs = set(self.name_expr.refs)
        if self.namespace_expr is not None:
            refs |= self.namespace_expr.refs
        return refs <= {"resourceId"}


@dataclass
class PostFilter:
    rel: RelationshipExpr


@dataclass
class UpdateSet:
    preconditions_exist: list[RelationshipExpr] = field(default_factory=list)
    preconditions_do_not_exist: list[RelationshipExpr] = field(default_factory=list)
    creates: list[RelationshipExpr] = field(default_factory=list)
    touches: list[RelationshipExpr] = field(default_factory=list)
    deletes: list[RelationshipExpr] = field(default_factory=list)
    delete_by_filter: list[RelationshipExpr] = field(default_factory=list)

    def empty(self) -> bool:
        return not (self.creates or self.touches or self.deletes
                    or self.delete_by_filter)


@dataclass
class RunnableRule:
    """A precompiled rule (reference RunnableRule, rules.go:657-666)."""

    name: str
    locking: str = ""
    ifs: list[CompiledExpr] = field(default_factory=list)
    checks: list[RelationshipExpr] = field(default_factory=list)
    post_checks: list[RelationshipExpr] = field(default_factory=list)
    pre_filters: list[PreFilter] = field(default_factory=list)
    post_filters: list[PostFilter] = field(default_factory=list)
    update: UpdateSet = field(default_factory=UpdateSet)

    def conditions_pass(self, input: ResolveInput) -> bool:
        """All `if` expressions must evaluate true (reference
        EvaluateCELConditions, rules.go:417-464)."""
        if not self.ifs:
            return True
        data = input.condition_data()
        return all(c.evaluate_bool(data) for c in self.ifs)


def _compile_rel_string(tpl: str) -> RelExpr:
    try:
        f_ = parse_rel_fields(tpl)
    except TupleError as e:
        raise CompileError(str(e)) from None
    return RelExpr(
        compile_template(f_["resource_type"]),
        compile_template(f_["resource_id"]),
        compile_template(f_["relation"]),
        compile_template(f_["subject_type"]),
        compile_template(f_["subject_id"]),
        compile_template(f_["subject_relation"]) if f_["subject_relation"] else None,
    )


def _compile_sot(sot: StringOrTemplate) -> RelationshipExpr:
    try:
        if sot.template:
            return _compile_rel_string(sot.template)
        if sot.tuple_set:
            return TupleSetExpr(compile_expr(sot.tuple_set))
        rt = sot.rel_template
        if rt:
            res, sub = rt["resource"], rt["subject"]
            return RelExpr(
                compile_template(str(res.get("type", ""))),
                compile_template(str(res.get("id", ""))),
                compile_template(str(res.get("relation", ""))),
                compile_template(str(sub.get("type", ""))),
                compile_template(str(sub.get("id", ""))),
                (compile_template(str(sub["relation"]))
                 if sub.get("relation") else None),
            )
    except ExprError as e:
        raise CompileError(str(e)) from None
    raise CompileError("empty StringOrTemplate")


def _compile_sot_rel(sot: StringOrTemplate, where: str) -> RelExpr:
    e = _compile_sot(sot)
    if not isinstance(e, RelExpr):
        raise CompileError(f"{where}: tupleSet is not allowed here")
    return e


def _compile_prefilter(p: PreFilterSpec, where: str) -> PreFilter:
    try:
        name_expr = compile_template(p.from_object_id_name_expr)
        ns_expr = (compile_template(p.from_object_id_namespace_expr)
                   if p.from_object_id_namespace_expr else None)
    except ExprError as e:
        raise CompileError(f"{where}: {e}") from None
    rel = _compile_sot_rel(p.lookup_matching_resources, where)
    return PreFilter(name_expr, ns_expr, rel)


def compile_rule(cfg: RuleConfig) -> RunnableRule:
    """Compile one rule config (reference Compile, rules.go:716-897)."""
    s = cfg.spec
    where = f"rule {cfg.name!r}"
    try:
        ifs = [compile_expr(c) for c in s.ifs]
    except ExprError as e:
        raise CompileError(f"{where}: if: {e}") from None
    upd: UpdateSpec = s.update
    return RunnableRule(
        name=cfg.name,
        locking=s.locking,
        ifs=ifs,
        checks=[_compile_sot(c) for c in s.checks],
        post_checks=[_compile_sot(c) for c in s.post_checks],
        pre_filters=[
            _compile_prefilter(p, f"{where}: prefilter") for p in s.pre_filters
        ],
        post_filters=[
            PostFilter(_compile_sot(p.check_permission_template))
            for p in s.post_filters
        ],
        update=UpdateSet(
            preconditions_exist=[
                _compile_sot_rel(x, f"{where}: preconditionExists")
                for x in upd.precondition_exists
            ],
            preconditions_do_not_exist=[
                _compile_sot_rel(x, f"{where}: preconditionDoesNotExist")
                for x in upd.precondition_does_not_exist
            ],
            creates=[_compile_sot(x) for x in upd.creates],
            touches=[_compile_sot(x) for x in upd.touches],
            deletes=[_compile_sot(x) for x in upd.deletes],
            delete_by_filter=[
                _compile_sot_rel(x, f"{where}: deleteByFilter")
                for x in upd.delete_by_filter
            ],
        ),
    )
