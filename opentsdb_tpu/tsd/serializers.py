"""HttpSerializer SPI + the default JSON implementation.

Reference behavior: /root/reference/src/tsd/HttpSerializer.java (:930,
pluggable parse/format per endpoint) and HttpJsonSerializer.java (:1283 —
parsePutV1 :~200, parseQueryV1 :250, formatQueryAsyncV1 :516 producing
[{metric, tags, aggregateTags, tsuids?, annotations?, dps}], error envelope).
Serializers register by name; requests pick one via the `serializer` query
param (HttpQuery.setSerializer).
"""

from __future__ import annotations

from opentsdb_tpu.models.tsquery import (
    TSQuery, TSSubQuery, parse_m_subquery, parse_tsuid_subquery,
    parse_rate_options, parse_percentiles)
from opentsdb_tpu.obs.registry import REGISTRY
from opentsdb_tpu.query.filters import build_filter, tags_to_filters
from opentsdb_tpu.query.planner import emit_texts
from opentsdb_tpu.tsd.http import BadRequestError, HttpQuery, RawJson


class HttpSerializer:
    """Base SPI: every hook raises 501 unless the subclass implements it."""

    name = "unknown"
    request_content_type = "application/json"
    response_content_type = "application/json; charset=UTF-8"

    def __init__(self, query: HttpQuery | None = None):
        self.query = query

    def shutdown(self) -> None:
        pass

    @classmethod
    def descriptor(cls) -> dict:
        """/api/serializers entry (HttpSerializer.java doc)."""
        parsers = [m[len("parse_"):-len("_v1")] for m in dir(cls)
                   if m.startswith("parse_") and m.endswith("_v1")]
        formatters = [m[len("format_"):-len("_v1")] for m in dir(cls)
                      if m.startswith("format_") and m.endswith("_v1")]
        return {
            "serializer": cls.name,
            "class": cls.__name__,
            "request_content_type": cls.request_content_type,
            "response_content_type": cls.response_content_type,
            "parsers": sorted(parsers),
            "formatters": sorted(formatters),
        }

    def __getattr__(self, item):
        if item.startswith(("parse_", "format_")):
            raise BadRequestError(
                "The requested API endpoint has not been implemented",
                status=501,
                details="The serializer %s has not implemented %s"
                        % (self.name, item))
        raise AttributeError(item)


class HttpJsonSerializer(HttpSerializer):
    """Default JSON (de)serializer."""

    name = "json"

    # -- parsers --

    def parse_put_v1(self) -> list[dict]:
        """POST /api/put body: one datapoint object or a list of them."""
        body = self.query.json_body()
        if isinstance(body, dict):
            body = [body]
        if not isinstance(body, list):
            raise BadRequestError("Unparseable data content",
                                  details="Expected a JSON object or array")
        for dp in body:
            if not isinstance(dp, dict):
                raise BadRequestError("Unparseable data content",
                                      details="Expected datapoint objects")
        return body

    def parse_suggest_v1(self) -> dict:
        body = self.query.json_body()
        if not isinstance(body, dict):
            raise BadRequestError("Unparseable data content")
        return body

    def parse_query_v1(self) -> TSQuery:
        """POST /api/query body -> TSQuery (HttpJsonSerializer.parseQueryV1)."""
        body = self.query.json_body()
        return ts_query_from_json(body)

    def parse_annotation_v1(self) -> dict:
        body = self.query.json_body()
        if not isinstance(body, dict):
            raise BadRequestError("Unparseable data content")
        return body

    def parse_annotation_bulk_v1(self) -> list[dict]:
        body = self.query.json_body()
        if isinstance(body, dict):
            return [body]
        if not isinstance(body, list):
            raise BadRequestError("Annotations must be in an array to bulk "
                                  "process")
        return body

    def parse_search_query_v1(self) -> dict:
        body = self.query.json_body()
        if not isinstance(body, dict):
            raise BadRequestError("Unparseable data content")
        return body

    def parse_uid_assign_v1(self) -> dict[str, list[str]]:
        """POST /api/uid/assign body {metric: [...], tagk: [...], tagv: [...]}."""
        body = self.query.json_body()
        if not isinstance(body, dict):
            raise BadRequestError("Unparseable data content")
        out = {}
        for kind, names in body.items():
            if isinstance(names, str):
                names = [names]
            out[kind] = list(names)
        return out

    def parse_uid_rename_v1(self) -> dict:
        body = self.query.json_body()
        if not isinstance(body, dict):
            raise BadRequestError("Unparseable data content")
        return body

    # -- formatters (each returns a JSON-able object; HttpQuery renders) --

    def format_put_v1(self, results: dict) -> dict:
        return results

    def format_suggest_v1(self, suggestions: list[str]) -> list[str]:
        return suggestions

    def format_aggregators_v1(self, aggregators: list[str]) -> list[str]:
        return aggregators

    def format_serializers_v1(self, serializers: list[dict]) -> list[dict]:
        return serializers

    def format_version_v1(self, version: dict) -> dict:
        return version

    def format_dropcaches_v1(self, response: dict) -> dict:
        return response

    def format_config_v1(self, config: dict) -> dict:
        return config

    def format_stats_v1(self, stats: list[dict]) -> list[dict]:
        return stats

    def format_query_stats_v1(self, stats: dict) -> dict:
        return stats

    def format_annotation_v1(self, note: dict) -> dict:
        return note

    def format_annotations_v1(self, notes: list[dict]) -> list[dict]:
        return notes

    def format_uid_assign_v1(self, response: dict) -> dict:
        return response

    def format_uid_rename_v1(self, response: dict) -> dict:
        return response

    def format_search_results_v1(self, results: dict) -> dict:
        return results

    def format_query_v1(self, data_query: TSQuery, results: list,
                        globals_list: list | None = None) -> list[dict]:
        """The /api/query result array (formatQueryAsyncV1 :516)."""
        out = []
        keys: dict = {}     # timestamps -> their key strings, shared
        plain = not (data_query.show_tsuids or data_query.show_query
                     or data_query.global_annotations)
        texts = (emit_texts(results, data_query.ms_resolution) if plain
                 else [None] * len(results))
        native = len(texts) - texts.count(None)
        lanes = REGISTRY.counter(
            "tsd.query.emit_groups", "Results written into /api/query "
            "answers, by the lane that wrote their points")
        lanes.labels(lane="native").inc(native)
        lanes.labels(lane="python").inc(len(results) - native)
        for r, text in zip(results, texts):
            if text is None and plain and not r.annotations:
                text = r.json_text(keys, data_query.ms_resolution)
            if text is not None:
                out.append(RawJson(text))
                continue
            out.append(r.to_json(
                keys=keys,
                ms_resolution=data_query.ms_resolution,
                show_tsuids=data_query.show_tsuids,
                fill_policy=(data_query.queries[r.index].fill_policy
                             if r.index < len(data_query.queries) else "none"),
                show_query=data_query.show_query,
                sub_query=(data_query.queries[r.index]
                           if r.index < len(data_query.queries) else None),
                no_annotations=data_query.no_annotations,
                global_annotations=data_query.global_annotations))
        return out

    def format_last_point_query_v1(self, results: list[dict]) -> list[dict]:
        return results


def ts_query_from_json(body) -> TSQuery:
    """JSON /api/query body -> TSQuery object model."""
    if not isinstance(body, dict):
        raise BadRequestError("Unparseable data content",
                              details="Expected a JSON object")
    if "queries" not in body or not body["queries"]:
        raise BadRequestError("Missing queries")
    q = TSQuery(
        start=str(body.get("start", "")),
        end=str(body["end"]) if body.get("end") not in (None, "") else None,
        timezone=body.get("timezone"),
        ms_resolution=bool(body.get("msResolution",
                                    body.get("ms", False))),
        show_tsuids=bool(body.get("showTSUIDs", False)),
        no_annotations=bool(body.get("noAnnotations", False)),
        global_annotations=bool(body.get("globalAnnotations", False)),
        show_summary=bool(body.get("showSummary", False)),
        show_stats=bool(body.get("showStats", False)),
        show_query=bool(body.get("showQuery", False)),
        delete=bool(body.get("delete", False)),
        use_calendar=bool(body.get("useCalendar", False)),
    )
    for i, sq in enumerate(body["queries"]):
        q.queries.append(sub_query_from_json(sq, i))
    return q


def sub_query_from_json(sq: dict, index: int) -> TSSubQuery:
    if not isinstance(sq, dict):
        raise BadRequestError("Unparseable sub query")
    sub = TSSubQuery(
        aggregator=sq.get("aggregator", ""),
        metric=sq.get("metric"),
        tsuids=sq.get("tsuids"),
        downsample=sq.get("downsample"),
        rate=bool(sq.get("rate", False)),
        explicit_tags=bool(sq.get("explicitTags", False)),
        pre_aggregate=bool(sq.get("preAggregate", False)),
        rollup_usage=sq.get("rollupUsage"),
        index=index,
    )
    ro = sq.get("rateOptions")
    if ro:
        from opentsdb_tpu.ops.rate import RateOptions
        sub.rate_options = RateOptions(
            counter=bool(ro.get("counter", False)),
            counter_max=int(ro.get("counterMax", RateOptions().counter_max)),
            reset_value=int(ro.get("resetValue", 0)),
            drop_resets=bool(ro.get("dropResets", False)))
    filters = []
    for f in sq.get("filters", []) or []:
        filters.append(build_filter(
            f["tagk"], f.get("type", "literal_or"), f.get("filter", ""),
            group_by=bool(f.get("groupBy", False))))
    # legacy "tags" map (2.1-style {host: "web01"} / {host: "*"})
    tags = sq.get("tags") or {}
    if tags:
        tags_to_filters(dict(tags), filters)
    sub.filters = filters
    pct = sq.get("percentiles")
    if pct:
        sub.percentiles = [float(p) for p in pct]
    sub.show_histogram_buckets = bool(sq.get("showHistogramBuckets", False))
    return sub


SERIALIZERS: dict[str, type[HttpSerializer]] = {
    HttpJsonSerializer.name: HttpJsonSerializer,
}


def register_serializer(cls: type[HttpSerializer]) -> None:
    existing = SERIALIZERS.get(cls.name)
    if existing is not None and existing is not cls:
        raise ValueError(
            "Serializer name collision: %s already registered by %s"
            % (cls.name, existing.__name__))
    SERIALIZERS[cls.name] = cls


def serializer_for(query: HttpQuery) -> HttpSerializer:
    name = query.get_query_string_param("serializer") or "json"
    cls = SERIALIZERS.get(name)
    if cls is None:
        raise BadRequestError("Could not find a serializer named: %s" % name,
                              status=400)
    return cls(query)
