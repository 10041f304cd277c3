"""Secret rule model and built-in ruleset.

Rule semantics follow the reference's model (ref: pkg/fanal/secret/scanner.go:89-100):
each rule has an ID/category/severity/title, a detection regex, a keyword
prefilter list (cheap lowercase substring check before the regex runs), an
optional path regex restricting which files it applies to, optional per-rule
allow rules, an optional exclude-block regex suppressing matches inside
matching block spans, and an optional named group selecting the secret span
within the regex match.

The built-in ruleset covers the same secret families as the reference's 87
built-in rules (ref: pkg/fanal/secret/builtin-rules.go) — cloud provider keys,
VCS tokens, SaaS API keys, private-key blocks — written independently from the
public token formats. Keywords are chosen to be substrings of any match so the
TPU keyword prefilter (exact substring search on device) is sound: a chunk
with no keyword hit can be skipped without running the regex at all.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from functools import cached_property

from trivy_tpu.types import Severity

import re._compiler as _sre_compile
import re._constants as _sre_c
import re._parser as _sre_parse

_SRE_MAXWIDTH = _sre_parse.MAXWIDTH

# Matches must not start mid-word: a token preceded by [0-9a-zA-Z] is part of a
# longer word and not a credential boundary (ref: builtin-rules.go:81 startWord).
_WORD_PREFIX = r"(?:^|[^0-9a-zA-Z])"

# Name of the regex group holding the secret when a rule wraps its payload.
SECRET_GROUP = "secret"

# ASCII-only case fold (bytes A-Z -> a-z, nothing else). The device keyword
# prefilter can only fold bytes 0x41-0x5A, so the host pre-lowering of
# keywords AND the content lowering the keyword test runs against must use
# the exact same fold — str.lower()'s unicode/locale folds (e.g. 'À'→'à',
# 'İ'→'i̇') would make host and device disagree on non-ASCII bytes, which
# for a custom rule is a silent device false negative.
_ASCII_LOWER_BYTES = bytes(
    c + 32 if 0x41 <= c <= 0x5A else c for c in range(256)
)


def ascii_lower(s: str) -> str:
    """Fold A-Z to a-z byte-wise; all other characters (including latin-1
    accented letters) pass through unchanged. ``s`` must be latin-1-safe
    (scan content is latin-1-decoded bytes, so it always is); the bytes
    round-trip keeps the fold C-speed on multi-MB content."""
    return s.encode("latin-1").translate(_ASCII_LOWER_BYTES).decode("latin-1")


def ascii_lower_any(s: str) -> str:
    """:func:`ascii_lower` for strings that may contain non-latin-1 chars
    (user-supplied keywords): folds A-Z, passes everything else through."""
    return "".join(
        chr(ord(c) + 32) if "A" <= c <= "Z" else c for c in s
    )


def ws(pattern: str) -> str:
    """Wrap ``pattern`` so it only matches at a word start, capturing the
    payload in the ``secret`` group. Mirrors the reference's
    ``MustCompileWithoutWordPrefix`` (ref: pkg/fanal/secret/scanner.go:66-68)."""
    return f"{_WORD_PREFIX}(?P<{SECRET_GROUP}>{pattern})"


def kw(name: str, secret: str, guard: str | None = None) -> str:
    """Keyword-context rule: ``name`` within ~25 chars of an assignment
    operator, payload captured in the ``secret`` group. ``guard`` is a
    character-class body asserting the payload is not a prefix of a longer
    run (compiles to ``(?:[^guard]|$)`` — the end alternative makes the
    rule end-anchored, so the engine gives it the full-content scan path).
    One definition for all keyword-context rules so the window/guard shape
    has a single audit point."""
    g = f"(?:[^{guard}]|$)" if guard else ""
    return (
        rf"(?i){name}[a-z0-9_\-\s\"']{{0,25}}[=:][\s\"']{{0,5}}"
        rf"(?P<{SECRET_GROUP}>{secret}){g}"
    )


@dataclass
class AllowRule:
    """Suppression rule (ref: pkg/fanal/secret/builtin-allow-rules.go).

    ``path``: files whose path matches are skipped entirely.
    ``regex``: tested against the *extracted secret text* of each candidate
    location (ref: scanner.go AllowLocation semantics); a match suppresses the
    finding. Anchors (``^``/``$``) therefore refer to the secret's own bounds.
    """

    id: str
    description: str = ""
    path: str | None = None
    regex: str | None = None

    @cached_property
    def path_re(self) -> re.Pattern | None:
        return re.compile(self.path) if self.path else None

    @cached_property
    def regex_re(self) -> re.Pattern | None:
        return re.compile(self.regex) if self.regex else None


@dataclass
class Rule:
    id: str
    category: str
    title: str
    severity: Severity
    regex: str
    keywords: list[str] = field(default_factory=list)
    path: str | None = None
    secret_group_name: str | None = None
    allow_rules: list[AllowRule] = field(default_factory=list)
    exclude_blocks: list[str] = field(default_factory=list)

    @cached_property
    def regex_re(self) -> re.Pattern:
        return re.compile(self.regex)

    @cached_property
    def path_re(self) -> re.Pattern | None:
        return re.compile(self.path) if self.path else None

    @cached_property
    def exclude_block_res(self) -> list[re.Pattern]:
        return [re.compile(p) for p in self.exclude_blocks]

    @cached_property
    def max_match_width(self) -> int | None:
        """Upper bound on a match's length in chars, or None if unbounded
        (used to size span-restricted confirmation windows)."""
        try:
            sre_parse = _sre_parse

            _, hi = sre_parse.parse(self.regex).getwidth()
            return None if hi >= _SRE_MAXWIDTH else int(hi)
        except Exception:
            return None

    @cached_property
    def start_detector(self) -> tuple[re.Pattern, int] | None:
        """Bounded *match-start detector* for unbounded-width rules.

        A compiled prefix of the pattern — truncated at the first
        unbounded repeat — such that any full-pattern match at position
        ``p`` implies the detector matches at ``p``, with finite max
        width.  The windowed confirm path (engine.find_rule_locations_in
        _windows) uses it to locate candidate starts inside device-flagged
        chunks and then runs the true regex via ``match(content, start)``
        for the exact unbounded extent, instead of rescanning the whole
        file (ref: the full-scan hot loop pkg/fanal/secret/scanner.go:377
        that this replaces).

        Returns ``(pattern, max_width)`` or None when no useful bounded
        prefix exists (unbounded from the first element) — callers then
        fall back to a full-content scan.
        """
        try:
            sre_compile = _sre_compile
            sre_parse = _sre_parse

            MAXW = _SRE_MAXWIDTH

            def item_width(state, op, av) -> int:
                probe = sre_parse.SubPattern(state, [(op, av)])
                return probe.getwidth()[1]

            def truncate(sub):
                """Longest bounded prefix of ``sub``'s concatenation;
                second value False when truncation happened (stop after)."""
                out = sre_parse.SubPattern(sub.state)
                for op, av in sub.data:
                    if item_width(sub.state, op, av) < MAXW:
                        out.data.append((op, av))
                        continue
                    name = str(op)
                    if name == "SUBPATTERN":
                        group, add_f, del_f, inner = av
                        tin, _ = truncate(inner)
                        if tin.data:
                            out.data.append((op, (group, add_f, del_f, tin)))
                    elif name in ("MAX_REPEAT", "MIN_REPEAT"):
                        lo, _hi, item = av
                        if lo > 0 and item.getwidth()[1] < MAXW:
                            out.data.append((op, (lo, lo, item)))
                    # anything else unbounded (branch, conditional): stop
                    # before it — the kept prefix is still a sound anchor
                    return out, False
                return out, True

            parsed = sre_parse.parse(self.regex)
            out, _ = truncate(parsed)
            if not out.data:
                return None
            _, width = out.getwidth()
            if width == 0 or width >= MAXW:
                return None
            return sre_compile.compile(out), int(width)
        except Exception:
            return None

    @cached_property
    def keyword_in_match(self) -> bool:
        """True when every match provably contains one of the rule's
        keywords (case-insensitively).

        Decides whether chunk-windowed confirmation is sound for the
        keyword device lane: the device flags chunks where a *keyword*
        occurs, so windows only cover match starts when the keyword is
        guaranteed to sit inside the match (within ``max_match_width`` of
        its start).  Proved by folding the pattern into mandatory
        lowercased character runs — a keyword inside a mandatory run is
        present in every match; anything unprovable returns False and the
        confirm falls back to a full-content scan (the reference's
        file-level keyword semantics, pkg/fanal/secret/scanner.go:174-186).
        """
        if not self.lower_keywords:
            return False
        try:
            sre_c, sre_parse = _sre_c, _sre_parse

            def fold_char(chars: frozenset) -> str | None:
                """Single char every member ASCII-folds to, or None — the
                same A-Z-only fold the keyword test uses."""
                folded = {
                    chr(_ASCII_LOWER_BYTES[c]) for c in chars if c < 256
                }
                return folded.pop() if len(folded) == 1 else None

            def single(op, av) -> frozenset | None:
                if op is sre_c.LITERAL:
                    return frozenset({av}) if av < 256 else None
                if op is sre_c.IN:
                    chars: set[int] = set()
                    for iop, iav in av:
                        if iop is sre_c.LITERAL and iav < 256:
                            chars.add(iav)
                        elif iop is sre_c.RANGE:
                            lo, hi = iav
                            chars.update(range(lo, min(hi, 255) + 1))
                        else:
                            return None
                    return frozenset(chars)
                return None

            MAX_PATHS = 64

            def walk(nodes, paths: list[list[str]]) -> None:
                """Accumulate mandatory folded fragments per alternation
                path; un-foldable constructs end the current fragment."""

                def append(text: str | None) -> None:
                    for p in paths:
                        if text is None:
                            if p[-1]:
                                p.append("")
                        else:
                            p[-1] += text

                for op, av in nodes:
                    name = str(op)
                    if name in ("LITERAL", "IN"):
                        cs = single(op, av)
                        append(fold_char(cs) if cs else None)
                    elif name in ("MAX_REPEAT", "MIN_REPEAT"):
                        lo, hi, sub = av
                        sub = list(sub)
                        ch = None
                        if lo > 0 and lo <= 256 and len(sub) == 1:
                            cs = single(*sub[0])
                            ch = fold_char(cs) if cs else None
                        append(ch * lo if ch else None)
                        if hi != lo:
                            append(None)
                    elif name == "SUBPATTERN":
                        _g, _af, _df, sub = av
                        walk(list(sub), paths)
                    elif name == "BRANCH":
                        _, alts = av
                        if len(paths) * len(alts) > MAX_PATHS:
                            append(None)
                            continue
                        forked: list[list[str]] = []
                        for alt in alts:
                            alt_paths = [list(p) for p in paths]
                            walk(list(alt), alt_paths)
                            forked.extend(alt_paths)
                        paths[:] = forked
                    else:
                        # AT/ASSERT/GROUPREF/...: conservatively break
                        append(None)

            paths: list[list[str]] = [[""]]
            walk(list(sre_parse.parse(self.regex)), paths)
            kws = self.lower_keywords
            return all(
                any(k in frag for frag in p for k in kws) for p in paths
            )
        except Exception:
            return False

    @cached_property
    def has_lookaround(self) -> bool:
        """True when the pattern contains lookahead/lookbehind assertions.
        Lookarounds contribute zero to getwidth(), so window-restricted
        scanning cannot bound the context they examine — such rules must take
        the full-content scan path to stay parity-identical."""
        try:
            sre_parse = _sre_parse

            def walk(items) -> bool:
                for op, av in items:
                    name = str(op)
                    if name in ("ASSERT", "ASSERT_NOT"):
                        return True
                    if isinstance(av, tuple):
                        for part in av:
                            if isinstance(part, sre_parse.SubPattern) and walk(part):
                                return True
                            if isinstance(part, (list, tuple)):
                                for sub in part:
                                    if isinstance(sub, sre_parse.SubPattern) and walk(sub):
                                        return True
                    elif isinstance(av, sre_parse.SubPattern) and walk(av):
                        return True
                return False

            return walk(sre_parse.parse(self.regex))
        except Exception:
            return True

    @cached_property
    def has_end_anchor(self) -> bool:
        """True when the pattern can match ``$``/``\\Z``. ``search(pos,
        endpos)`` treats endpos as end-of-string, so an end anchor matches at
        a window edge where the full scan (with real trailing content) would
        not — window-restricted scanning re-verifies such edge matches
        against the real string end (engine.find_rule_locations_in_windows)."""
        try:
            sre_c, sre_parse = _sre_c, _sre_parse

            def walk(items) -> bool:
                for op, av in items:
                    if op is sre_c.AT and av in (
                        sre_c.AT_END, sre_c.AT_END_STRING, sre_c.AT_END_LINE
                    ):
                        return True
                    if isinstance(av, tuple):
                        for part in av:
                            if isinstance(part, sre_parse.SubPattern) and walk(part):
                                return True
                            if isinstance(part, (list, tuple)):
                                for sub in part:
                                    if isinstance(sub, sre_parse.SubPattern) and walk(sub):
                                        return True
                    elif isinstance(av, sre_parse.SubPattern) and walk(av):
                        return True
                return False

            return walk(sre_parse.parse(self.regex))
        except Exception:
            return True

    @cached_property
    def lower_keywords(self) -> list[str]:
        # ASCII fold only — must equal the device prefilter's A-Z fold (see
        # ascii_lower); keywords are matched against ascii_lower(content)
        return [ascii_lower_any(k) for k in self.keywords]

    def match_path(self, path: str) -> bool:
        return self.path_re is None or self.path_re.search(path) is not None

    def allow_path(self, path: str) -> bool:
        return any(a.path_re and a.path_re.search(path) for a in self.allow_rules)

    def match_keywords(self, lower_content: str) -> bool:
        """Cheap prefilter: any keyword present (lowercased substring), or no
        keywords at all (ref: scanner.go:174-186)."""
        if not self.lower_keywords:
            return True
        return any(k in lower_content for k in self.lower_keywords)


def _r(
    id: str,
    category: str,
    title: str,
    severity: Severity,
    regex: str,
    keywords: list[str],
    **kw,
) -> Rule:
    return Rule(
        id=id, category=category, title=title, severity=severity, regex=regex,
        keywords=keywords, **kw,
    )


CategoryAWS = "AWS"
CategoryGitHub = "GitHub"
CategoryGitLab = "GitLab"
CategoryAsymmetricPrivateKey = "AsymmetricPrivateKey"
CategoryGoogle = "Google"
CategorySlack = "Slack"
CategoryStripe = "Stripe"
CategoryShopify = "Shopify"
CategoryGeneric = "Generic"


def builtin_rules() -> list[Rule]:
    """The built-in ruleset. Order is significant only for output sorting."""
    S = Severity
    rules: list[Rule] = [
        # ----- cloud providers -------------------------------------------------
        _r("aws-access-key-id", CategoryAWS, "AWS Access Key ID", S.CRITICAL,
           ws(r"(?:A3T[0-9A-Z]|AKIA|AGPA|AIDA|AROA|AIPA|ANPA|ANVA|ASIA)[0-9A-Z]{16}"),
           ["AKIA", "AGPA", "AIDA", "AROA", "AIPA", "ANPA", "ANVA", "ASIA"],
           secret_group_name=SECRET_GROUP,
           allow_rules=[AllowRule(id="aws-example-key",
                                  description="AWS documentation example keys",
                                  regex=r"EXAMPLE")]),
        _r("aws-secret-access-key", CategoryAWS, "AWS Secret Access Key", S.CRITICAL,
           r"(?i)(?:^|[^0-9a-zA-Z])aws[_\-\.]{0,25}(?:secret|sk)?[_\-\.]{0,25}"
           r"(?:access)?[_\-\.]{0,25}key(?:[_\-\.]{0,2}id)?[\s:=\"']{1,10}"
           r"(?P<secret>[0-9a-zA-Z/+]{40})(?:[^0-9a-zA-Z/+]|$)",
           ["aws"], secret_group_name=SECRET_GROUP,
           allow_rules=[AllowRule(id="aws-example-secret",
                                  description="AWS documentation example secrets",
                                  regex=r"EXAMPLEKEY")]),
        _r("aws-mws-key", CategoryAWS, "AWS Marketplace Web Service key", S.HIGH,
           ws(r"amzn\.mws\.[0-9a-f]{8}-[0-9a-f]{4}-[0-9a-f]{4}-[0-9a-f]{4}-[0-9a-f]{12}"),
           ["amzn.mws"], secret_group_name=SECRET_GROUP),
        _r("gcp-api-key", CategoryGoogle, "Google API key", S.HIGH,
           ws(r"AIza[0-9A-Za-z_\-]{35}"), ["AIza"], secret_group_name=SECRET_GROUP),
        _r("gcp-service-account", CategoryGoogle, "Google service account credentials", S.CRITICAL,
           r"\"type\"\s*:\s*\"service_account\"", ["service_account"]),
        _r("alibaba-access-key-id", "Alibaba", "Alibaba Cloud AccessKey ID", S.HIGH,
           ws(r"LTAI[0-9a-zA-Z]{12,24}"), ["LTAI"], secret_group_name=SECRET_GROUP),
        _r("azure-storage-account-key", "Azure", "Azure Storage account key", S.CRITICAL,
           r"(?i)AccountKey\s*=\s*(?P<secret>[0-9a-zA-Z+/=]{88})",
           ["AccountKey"], secret_group_name=SECRET_GROUP),
        _r("digitalocean-pat", "DigitalOcean", "DigitalOcean personal access token", S.CRITICAL,
           ws(r"dop_v1_[a-f0-9]{64}"), ["dop_v1_"], secret_group_name=SECRET_GROUP),
        _r("digitalocean-oauth-token", "DigitalOcean", "DigitalOcean OAuth token", S.CRITICAL,
           ws(r"doo_v1_[a-f0-9]{64}"), ["doo_v1_"], secret_group_name=SECRET_GROUP),
        _r("digitalocean-refresh-token", "DigitalOcean", "DigitalOcean refresh token", S.HIGH,
           ws(r"dor_v1_[a-f0-9]{64}"), ["dor_v1_"], secret_group_name=SECRET_GROUP),
        _r("heroku-api-key", "Heroku", "Heroku API key", S.HIGH,
           r"(?i)heroku[a-z0-9_\-\s\"']{0,25}(?:=|>|:=|\|\|:|<=|=>|:)[\s\"']{0,5}"
           r"(?P<secret>[0-9a-f]{8}-[0-9a-f]{4}-[0-9a-f]{4}-[0-9a-f]{4}-[0-9a-f]{12})",
           ["heroku"], secret_group_name=SECRET_GROUP),
        # ----- VCS / forges ----------------------------------------------------
        _r("github-pat", CategoryGitHub, "GitHub personal access token", S.CRITICAL,
           ws(r"ghp_[0-9a-zA-Z]{36}"), ["ghp_"], secret_group_name=SECRET_GROUP),
        _r("github-oauth", CategoryGitHub, "GitHub OAuth access token", S.CRITICAL,
           ws(r"gho_[0-9a-zA-Z]{36}"), ["gho_"], secret_group_name=SECRET_GROUP),
        _r("github-app-token", CategoryGitHub, "GitHub app token", S.CRITICAL,
           ws(r"(?:ghu|ghs)_[0-9a-zA-Z]{36}"), ["ghu_", "ghs_"], secret_group_name=SECRET_GROUP),
        _r("github-refresh-token", CategoryGitHub, "GitHub refresh token", S.CRITICAL,
           ws(r"ghr_[0-9a-zA-Z]{76}"), ["ghr_"], secret_group_name=SECRET_GROUP),
        _r("github-fine-grained-pat", CategoryGitHub, "GitHub fine-grained personal access token",
           S.CRITICAL, ws(r"github_pat_[0-9a-zA-Z_]{82}"), ["github_pat_"],
           secret_group_name=SECRET_GROUP),
        _r("gitlab-pat", CategoryGitLab, "GitLab personal access token", S.CRITICAL,
           ws(r"glpat-[0-9a-zA-Z_\-]{20}"), ["glpat-"], secret_group_name=SECRET_GROUP),
        _r("gitlab-runner-token", CategoryGitLab, "GitLab runner registration token", S.HIGH,
           ws(r"GR1348941[0-9a-zA-Z_\-]{20}"), ["GR1348941"], secret_group_name=SECRET_GROUP),
        _r("gitlab-pipeline-trigger-token", CategoryGitLab, "GitLab pipeline trigger token", S.HIGH,
           ws(r"glptt-[0-9a-f]{40}"), ["glptt-"], secret_group_name=SECRET_GROUP),
        # ----- key material ----------------------------------------------------
        _r("private-key", CategoryAsymmetricPrivateKey, "Asymmetric private key block", S.HIGH,
           r"-----BEGIN (?:RSA |EC |DSA |OPENSSH |PGP |ENCRYPTED )?PRIVATE KEY(?: BLOCK)?-----"
           r"(?P<secret>[\s\S]*?)-----END",
           ["-----BEGIN"], secret_group_name=SECRET_GROUP),
        _r("age-secret-key", "Age", "age encryption secret key", S.MEDIUM,
           ws(r"AGE-SECRET-KEY-1[0-9A-Z]{58}"), ["AGE-SECRET-KEY-1"],
           secret_group_name=SECRET_GROUP),
        _r("jwt-token", CategoryGeneric, "JSON Web Token", S.MEDIUM,
           ws(r"ey[a-zA-Z0-9_=]{14,}\.ey[a-zA-Z0-9_/+\-=]{14,}\.[a-zA-Z0-9_/+\-=]{10,}"),
           ["eyJ"], secret_group_name=SECRET_GROUP),
        # ----- chat / collaboration -------------------------------------------
        _r("slack-access-token", CategorySlack, "Slack token", S.HIGH,
           ws(r"xox[baprs]-(?:[0-9]{8,14}-){2,3}[0-9a-zA-Z]{18,34}"), ["xoxb-",
           "xoxa-", "xoxp-", "xoxr-", "xoxs-"], secret_group_name=SECRET_GROUP),
        _r("slack-app-token", CategorySlack, "Slack app-level token", S.HIGH,
           ws(r"xapp-[0-9]-[0-9A-Z]{8,12}-[0-9]{10,14}-[0-9a-f]{60,70}"), ["xapp-"],
           secret_group_name=SECRET_GROUP),
        _r("slack-web-hook", CategorySlack, "Slack incoming webhook URL", S.MEDIUM,
           r"https://hooks\.slack\.com/(?:services|workflows)/"
           r"[0-9A-Z]{8,12}/[0-9A-Z]{8,12}/[0-9a-zA-Z]{20,26}",
           ["hooks.slack.com"]),
        _r("discord-bot-token", "Discord", "Discord bot token", S.HIGH,
           r"(?i)discord[a-z0-9_\-\s\"']{0,25}[=:][\s\"']{0,5}"
           r"(?P<secret>[MNO][a-zA-Z0-9_\-]{23,25}\.[a-zA-Z0-9_\-]{6}\.[a-zA-Z0-9_\-]{27,38})",
           ["discord"], secret_group_name=SECRET_GROUP),
        _r("telegram-bot-token", "Telegram", "Telegram bot token", S.HIGH,
           r"(?i)telegram[a-z0-9_\-\s\"']{0,25}[=:][\s\"']{0,5}"
           r"(?P<secret>[0-9]{8,10}:[0-9A-Za-z_\-]{35})",
           ["telegram"], secret_group_name=SECRET_GROUP),
        # ----- payments --------------------------------------------------------
        _r("stripe-secret-token", CategoryStripe, "Stripe secret key", S.CRITICAL,
           ws(r"sk_(?:test|live)_[0-9a-zA-Z]{24,99}"), ["sk_test_", "sk_live_"],
           secret_group_name=SECRET_GROUP),
        _r("stripe-publishable-token", CategoryStripe, "Stripe publishable key", S.LOW,
           ws(r"pk_(?:test|live)_[0-9a-zA-Z]{24,99}"), ["pk_test_", "pk_live_"],
           secret_group_name=SECRET_GROUP),
        _r("square-access-token", "Square", "Square access token", S.HIGH,
           ws(r"sq0atp-[0-9A-Za-z_\-]{22}"), ["sq0atp-"], secret_group_name=SECRET_GROUP),
        _r("square-oauth-secret", "Square", "Square OAuth secret", S.HIGH,
           ws(r"sq0csp-[0-9A-Za-z_\-]{43}"), ["sq0csp-"], secret_group_name=SECRET_GROUP),
        _r("paypal-braintree-token", "PayPal", "Braintree access token", S.HIGH,
           ws(r"access_token\$production\$[0-9a-z]{16}\$[0-9a-f]{32}"),
           ["access_token$production$"], secret_group_name=SECRET_GROUP),
        _r("shopify-token", CategoryShopify, "Shopify token", S.CRITICAL,
           ws(r"shp(?:at|ca|pa|ss)_[0-9a-fA-F]{32}"),
           ["shpat_", "shpca_", "shppa_", "shpss_"], secret_group_name=SECRET_GROUP),
        # ----- email / messaging SaaS -----------------------------------------
        _r("sendgrid-api-token", "SendGrid", "SendGrid API key", S.HIGH,
           ws(r"SG\.[0-9A-Za-z_\-]{22}\.[0-9A-Za-z_\-]{43}"), ["SG."],
           secret_group_name=SECRET_GROUP),
        _r("mailgun-token", "Mailgun", "Mailgun private API token", S.HIGH,
           ws(r"key-[0-9a-f]{32}"), ["key-"], secret_group_name=SECRET_GROUP),
        _r("mailchimp-api-key", "Mailchimp", "Mailchimp API key", S.HIGH,
           ws(r"[0-9a-f]{32}-us[0-9]{1,2}"), ["-us"], secret_group_name=SECRET_GROUP),
        _r("twilio-api-key", "Twilio", "Twilio API key SID", S.HIGH,
           r"(?i)twilio[a-z0-9_\-\s\"']{0,25}[=:][\s\"']{0,5}(?P<secret>SK[0-9a-f]{32})",
           ["twilio"], secret_group_name=SECRET_GROUP),
        # ----- package registries ---------------------------------------------
        _r("npm-access-token", "npm", "npm access token", S.CRITICAL,
           ws(r"npm_[0-9a-zA-Z]{36}"), ["npm_"], secret_group_name=SECRET_GROUP),
        _r("pypi-upload-token", "PyPI", "PyPI upload token", S.HIGH,
           r"pypi-AgEIcHlwaS5vcmc[0-9A-Za-z_\-]{50,1000}", ["pypi-AgEIcHlwaS5vcmc"]),
        _r("rubygems-api-token", "RubyGems", "RubyGems API key", S.HIGH,
           ws(r"rubygems_[0-9a-f]{48}"), ["rubygems_"], secret_group_name=SECRET_GROUP),
        _r("clojars-api-token", "Clojars", "Clojars API token", S.HIGH,
           r"CLOJARS_[0-9a-z]{60}", ["CLOJARS_"]),
        # ----- CI / infra SaaS -------------------------------------------------
        _r("databricks-api-token", "Databricks", "Databricks API token", S.HIGH,
           ws(r"dapi[0-9a-h]{32}"), ["dapi"], secret_group_name=SECRET_GROUP),
        _r("hashicorp-tf-api-token", "HashiCorp", "Terraform Cloud / Vault API token", S.HIGH,
           ws(r"[0-9a-zA-Z]{14}\.atlasv1\.[0-9a-zA-Z_\-]{60,70}"), [".atlasv1."],
           secret_group_name=SECRET_GROUP),
        _r("dockerhub-pat", "Docker", "Docker Hub personal access token", S.HIGH,
           ws(r"dckr_pat_[0-9a-zA-Z_\-]{27}"), ["dckr_pat_"], secret_group_name=SECRET_GROUP),
        _r("grafana-api-token", "Grafana", "Grafana API token", S.MEDIUM,
           ws(r"eyJrIjoi[0-9a-zA-Z_=\-]{60,100}"), ["eyJrIjoi"], secret_group_name=SECRET_GROUP),
        _r("grafana-service-account-token", "Grafana", "Grafana service account token", S.MEDIUM,
           ws(r"glsa_[0-9a-zA-Z_]{32}_[0-9a-f]{8}"), ["glsa_"], secret_group_name=SECRET_GROUP),
        _r("new-relic-user-api-key", "NewRelic", "New Relic user API key", S.MEDIUM,
           ws(r"NRAK-[0-9A-Z]{27}"), ["NRAK-"], secret_group_name=SECRET_GROUP),
        _r("datadog-access-token", "Datadog", "Datadog access token", S.MEDIUM,
           r"(?i)datadog[a-z0-9_\-\s\"']{0,25}[=:][\s\"']{0,5}(?P<secret>[0-9a-f]{40})",
           ["datadog"], secret_group_name=SECRET_GROUP),
        _r("pulumi-api-token", "Pulumi", "Pulumi API token", S.HIGH,
           ws(r"pul-[0-9a-f]{40}"), ["pul-"], secret_group_name=SECRET_GROUP),
        _r("doppler-api-token", "Doppler", "Doppler API token", S.HIGH,
           ws(r"dp\.pt\.[0-9a-zA-Z]{43}"), ["dp.pt."], secret_group_name=SECRET_GROUP),
        _r("flyio-access-token", "Fly.io", "Fly.io access token", S.HIGH,
           ws(r"fo1_[0-9a-zA-Z_\-]{43}"), ["fo1_"], secret_group_name=SECRET_GROUP),
        # ----- AI / data SaaS --------------------------------------------------
        _r("openai-api-key", "OpenAI", "OpenAI API key", S.HIGH,
           ws(r"sk-[0-9a-zA-Z]{20}T3BlbkFJ[0-9a-zA-Z]{20}"), ["T3BlbkFJ"],
           secret_group_name=SECRET_GROUP),
        _r("hugging-face-access-token", "HuggingFace", "Hugging Face access token", S.HIGH,
           ws(r"hf_[a-zA-Z]{34}"), ["hf_"], secret_group_name=SECRET_GROUP),
        _r("anthropic-api-key", "Anthropic", "Anthropic API key", S.HIGH,
           ws(r"sk-ant-[a-zA-Z0-9_\-]{20,120}"), ["sk-ant-"], secret_group_name=SECRET_GROUP),
        # ----- misc SaaS -------------------------------------------------------
        _r("atlassian-api-token", "Atlassian", "Atlassian API token", S.HIGH,
           r"(?i)(?:atlassian|jira|confluence)[a-z0-9_\-\s\"']{0,25}[=:][\s\"']{0,5}"
           r"(?P<secret>[a-zA-Z0-9]{24})(?:[^a-zA-Z0-9]|$)",
           ["atlassian", "jira", "confluence"], secret_group_name=SECRET_GROUP),
        _r("asana-access-token", "Asana", "Asana personal access token", S.MEDIUM,
           r"(?i)asana[a-z0-9_\-\s\"']{0,25}[=:][\s\"']{0,5}"
           r"(?P<secret>[0-9]/[0-9]{10,16}:[0-9a-f]{32})",
           ["asana"], secret_group_name=SECRET_GROUP),
        _r("dropbox-short-lived-api-token", "Dropbox", "Dropbox short-lived API token", S.MEDIUM,
           ws(r"sl\.[0-9a-zA-Z_\-]{130,152}"), ["sl."], secret_group_name=SECRET_GROUP),
        _r("netlify-access-token", "Netlify", "Netlify access token", S.MEDIUM,
           r"(?i)netlify[a-z0-9_\-\s\"']{0,25}[=:][\s\"']{0,5}"
           r"(?P<secret>[0-9a-zA-Z_\-]{40,46})",
           ["netlify"], secret_group_name=SECRET_GROUP),
        _r("linear-api-token", "Linear", "Linear API token", S.MEDIUM,
           ws(r"lin_api_[0-9a-zA-Z]{40}"), ["lin_api_"], secret_group_name=SECRET_GROUP),
        _r("postman-api-token", "Postman", "Postman API token", S.MEDIUM,
           ws(r"PMAK-[0-9a-f]{24}-[0-9a-f]{34}"), ["PMAK-"], secret_group_name=SECRET_GROUP),
        _r("sentry-access-token", "Sentry", "Sentry auth token", S.MEDIUM,
           r"(?i)sentry[a-z0-9_\-\s\"']{0,25}[=:][\s\"']{0,5}(?P<secret>[0-9a-f]{64})",
           ["sentry"], secret_group_name=SECRET_GROUP),
        _r("facebook-token", "Facebook", "Facebook access token", S.HIGH,
           ws(r"EAACEdEose0cBA[0-9A-Za-z]+"), ["EAACEdEose0cBA"], secret_group_name=SECRET_GROUP),
        _r("twitter-bearer-token", "Twitter", "Twitter/X bearer token", S.MEDIUM,
           ws(r"AAAAAAAAAAAAAAAAAAAAA[0-9a-zA-Z%]{60,120}"), ["AAAAAAAAAAAAAAAAAAAAA"],
           secret_group_name=SECRET_GROUP),
        # ----- SaaS breadth (reference rule-ID parity set) --------------------
        _r("adobe-client-id", "Adobe", "Adobe client ID (OAuth web)", S.MEDIUM,
           kw("adobe", r"[a-f0-9]{32}", "a-f0-9"),
           ["adobe"], secret_group_name=SECRET_GROUP),
        _r("adobe-client-secret", "Adobe", "Adobe client secret", S.HIGH,
           ws(r"p8e-[a-z0-9]{32}"), ["p8e-"], secret_group_name=SECRET_GROUP),
        _r("alibaba-secret-key", "Alibaba", "Alibaba Cloud AccessKey secret", S.CRITICAL,
           kw("alibaba", r"[a-zA-Z0-9]{30}", "a-zA-Z0-9"),
           ["alibaba"], secret_group_name=SECRET_GROUP),
        _r("asana-client-id", "Asana", "Asana client ID", S.MEDIUM,
           kw("asana", r"[0-9]{16}", "0-9"),
           ["asana"], secret_group_name=SECRET_GROUP),
        _r("asana-client-secret", "Asana", "Asana client secret", S.HIGH,
           kw("asana", r"[a-z0-9]{32}", "a-z0-9"),
           ["asana"], secret_group_name=SECRET_GROUP),
        _r("beamer-api-token", "Beamer", "Beamer API token", S.MEDIUM,
           kw("beamer", r"b_[a-z0-9=_\-]{44}"),
           ["beamer"], secret_group_name=SECRET_GROUP),
        _r("bitbucket-client-id", "Bitbucket", "Bitbucket client ID", S.MEDIUM,
           kw("bitbucket", r"[a-zA-Z0-9]{32}", "a-zA-Z0-9"),
           ["bitbucket"], secret_group_name=SECRET_GROUP),
        _r("bitbucket-client-secret", "Bitbucket", "Bitbucket client secret", S.HIGH,
           kw("bitbucket", r"[a-zA-Z0-9=_\-]{64}", r"a-zA-Z0-9=_\-"),
           ["bitbucket"], secret_group_name=SECRET_GROUP),
        _r("contentful-delivery-api-token", "Contentful", "Contentful delivery API token",
           S.MEDIUM, ws(r"CFPAT-[a-zA-Z0-9_\-]{43}"), ["CFPAT-"],
           secret_group_name=SECRET_GROUP),
        _r("discord-api-token", "Discord", "Discord API key", S.HIGH,
           kw("discord", r"[a-f0-9]{64}", "a-f0-9"),
           ["discord"], secret_group_name=SECRET_GROUP),
        _r("discord-client-id", "Discord", "Discord client ID", S.LOW,
           kw("discord", r"[0-9]{18}", "0-9"),
           ["discord"], secret_group_name=SECRET_GROUP),
        _r("discord-client-secret", "Discord", "Discord client secret", S.HIGH,
           kw("discord", r"[a-zA-Z0-9=_\-]{32}", r"a-zA-Z0-9=_\-"),
           ["discord"], secret_group_name=SECRET_GROUP),
        _r("dockerconfig-secret", "Docker", "Dockerconfig secret", S.HIGH,
           r"(?i)(?:\.dockerconfigjson|\.dockercfg)[\s\"']{0,5}:[\s\"']{0,5}"
           r"(?P<secret>[A-Za-z0-9+/=]{40,4000})",
           [".dockerconfigjson", ".dockercfg"], secret_group_name=SECRET_GROUP),
        _r("dropbox-api-secret", "Dropbox", "Dropbox API secret", S.HIGH,
           kw("dropbox", r"[a-z0-9]{15}", "a-z0-9"),
           ["dropbox"], secret_group_name=SECRET_GROUP),
        _r("dropbox-long-lived-api-token", "Dropbox", "Dropbox long-lived API token", S.HIGH,
           kw("dropbox", r"[a-z0-9]{11}(?:AAAAAAAAAA)[a-z0-9\-_=]{43}"),
           ["dropbox"], secret_group_name=SECRET_GROUP),
        _r("duffel-api-token", "Duffel", "Duffel API token", S.HIGH,
           ws(r"duffel_(?:test|live)_[a-zA-Z0-9_\-=]{43}"), ["duffel_"],
           secret_group_name=SECRET_GROUP),
        _r("dynatrace-api-token", "Dynatrace", "Dynatrace API token", S.HIGH,
           ws(r"dt0c01\.[a-zA-Z0-9]{24}\.[a-z0-9]{64}"), ["dt0c01."],
           secret_group_name=SECRET_GROUP),
        _r("easypost-api-token", "EasyPost", "EasyPost API token", S.HIGH,
           ws(r"EZ[AT]K[a-zA-Z0-9]{54}"), ["EZAK", "EZTK"],
           secret_group_name=SECRET_GROUP),
        _r("fastly-api-token", "Fastly", "Fastly API token", S.HIGH,
           kw("fastly", r"[a-zA-Z0-9=_\-]{32}", r"a-zA-Z0-9=_\-"),
           ["fastly"], secret_group_name=SECRET_GROUP),
        _r("finicity-api-token", "Finicity", "Finicity API token", S.HIGH,
           kw("finicity", r"[a-f0-9]{32}", "a-f0-9"),
           ["finicity"], secret_group_name=SECRET_GROUP),
        _r("finicity-client-secret", "Finicity", "Finicity client secret", S.HIGH,
           kw("finicity", r"[a-z0-9]{20}", "a-z0-9"),
           ["finicity"], secret_group_name=SECRET_GROUP),
        _r("flutterwave-enc-key", "Flutterwave", "Flutterwave encryption key", S.HIGH,
           ws(r"FLWSECK_TEST-[a-h0-9]{12}"), ["FLWSECK_TEST"],
           secret_group_name=SECRET_GROUP),
        _r("flutterwave-public-key", "Flutterwave", "Flutterwave public key", S.MEDIUM,
           ws(r"FLWPUBK_TEST-[a-h0-9]{32}-X"), ["FLWPUBK_TEST"],
           secret_group_name=SECRET_GROUP),
        _r("frameio-api-token", "Frame.io", "Frame.io API token", S.HIGH,
           ws(r"fio-u-[a-zA-Z0-9\-_=]{64}"), ["fio-u-"], secret_group_name=SECRET_GROUP),
        _r("gocardless-api-token", "GoCardless", "GoCardless API token", S.HIGH,
           kw("gocardless", r"live_[a-zA-Z0-9\-_=]{40}"),
           ["gocardless"], secret_group_name=SECRET_GROUP),
        _r("hubspot-api-token", "HubSpot", "HubSpot API token", S.HIGH,
           kw("hubspot",
              r"[a-h0-9]{8}-[a-h0-9]{4}-[a-h0-9]{4}-[a-h0-9]{4}-[a-h0-9]{12}"),
           ["hubspot"], secret_group_name=SECRET_GROUP),
        _r("intercom-api-token", "Intercom", "Intercom API token", S.HIGH,
           kw("intercom", r"[a-zA-Z0-9=_]{60}", "a-zA-Z0-9=_"),
           ["intercom"], secret_group_name=SECRET_GROUP),
        _r("intercom-client-secret", "Intercom", "Intercom client secret/ID", S.HIGH,
           kw("intercom",
              r"[a-h0-9]{8}-[a-h0-9]{4}-[a-h0-9]{4}-[a-h0-9]{4}-[a-h0-9]{12}"),
           ["intercom"], secret_group_name=SECRET_GROUP),
        _r("ionic-api-token", "Ionic", "Ionic API token", S.HIGH,
           ws(r"ion_[a-z0-9]{42}"), ["ion_"], secret_group_name=SECRET_GROUP),
        _r("linear-client-secret", "Linear", "Linear client secret", S.HIGH,
           kw("linear", r"[a-f0-9]{32}", "a-f0-9"),
           ["linear"], secret_group_name=SECRET_GROUP),
        _r("linkedin-client-id", "LinkedIn", "LinkedIn client ID", S.MEDIUM,
           kw(r"linked[_\-]?in", r"[a-z0-9]{14}", "a-z0-9"),
           ["linkedin", "linked_in", "linked-in"], secret_group_name=SECRET_GROUP),
        _r("linkedin-client-secret", "LinkedIn", "LinkedIn client secret", S.HIGH,
           kw(r"linked[_\-]?in", r"[a-z0-9]{16}", "a-z0-9"),
           ["linkedin", "linked_in", "linked-in"], secret_group_name=SECRET_GROUP),
        _r("lob-api-key", "Lob", "Lob API key", S.HIGH,
           kw("lob", r"(?:live|test)_[a-f0-9]{35}"),
           ["lob"], secret_group_name=SECRET_GROUP),
        _r("lob-pub-api-key", "Lob", "Lob publishable API key", S.MEDIUM,
           kw("lob", r"(?:test|live)_pub_[a-f0-9]{31}"),
           ["lob"], secret_group_name=SECRET_GROUP),
        _r("mailgun-signing-key", "Mailgun", "Mailgun webhook signing key", S.HIGH,
           kw("mailgun", r"[a-h0-9]{32}-[a-h0-9]{8}-[a-h0-9]{8}"),
           ["mailgun"], secret_group_name=SECRET_GROUP),
        _r("mapbox-api-token", "Mapbox", "Mapbox API token", S.MEDIUM,
           kw("mapbox", r"pk\.[a-z0-9]{60}\.[a-z0-9]{22}"),
           ["mapbox"], secret_group_name=SECRET_GROUP),
        _r("messagebird-api-token", "MessageBird", "MessageBird API token", S.HIGH,
           kw(r"message[_\-]?bird", r"[a-z0-9]{25}", "a-z0-9"),
           ["messagebird", "message_bird", "message-bird"],
           secret_group_name=SECRET_GROUP),
        _r("messagebird-client-id", "MessageBird", "MessageBird client ID", S.MEDIUM,
           kw(r"message[_\-]?bird",
              r"[a-h0-9]{8}-[a-h0-9]{4}-[a-h0-9]{4}-[a-h0-9]{4}-[a-h0-9]{12}"),
           ["messagebird", "message_bird", "message-bird"],
           secret_group_name=SECRET_GROUP),
        _r("new-relic-browser-api-token", "NewRelic", "New Relic ingest browser API token",
           S.MEDIUM, ws(r"NRJS-[a-f0-9]{19}"), ["NRJS-"], secret_group_name=SECRET_GROUP),
        _r("new-relic-user-api-id", "NewRelic", "New Relic user API ID", S.MEDIUM,
           kw(r"(?:new[_\-]?relic|nrak)", r"[A-Z0-9]{64}", "A-Z0-9"),
           ["newrelic", "new_relic", "new-relic", "nrak"],
           secret_group_name=SECRET_GROUP),
        _r("planetscale-api-token", "PlanetScale", "PlanetScale API token", S.HIGH,
           ws(r"pscale_tkn_[a-zA-Z0-9\-_\.]{43}"), ["pscale_tkn_"],
           secret_group_name=SECRET_GROUP),
        _r("planetscale-password", "PlanetScale", "PlanetScale password", S.HIGH,
           ws(r"pscale_pw_[a-zA-Z0-9\-_\.]{43}"), ["pscale_pw_"],
           secret_group_name=SECRET_GROUP),
        _r("private-packagist-token", "Packagist", "Private Packagist token", S.HIGH,
           ws(r"packagist_[ou][ru]t_[a-f0-9]{68}"), ["packagist_"],
           secret_group_name=SECRET_GROUP),
        _r("sendinblue-api-token", "Sendinblue", "Sendinblue API token", S.HIGH,
           ws(r"xkeysib-[a-f0-9]{64}-[a-zA-Z0-9]{16}"), ["xkeysib-"],
           secret_group_name=SECRET_GROUP),
        _r("shippo-api-token", "Shippo", "Shippo API token", S.HIGH,
           ws(r"shippo_(?:live|test)_[a-f0-9]{40}"), ["shippo_"],
           secret_group_name=SECRET_GROUP),
        _r("twitch-api-token", "Twitch", "Twitch API token", S.HIGH,
           kw("twitch", r"[a-z0-9]{30}", "a-z0-9"),
           ["twitch"], secret_group_name=SECRET_GROUP),
        _r("twitter-token", "Twitter", "Twitter token", S.MEDIUM,
           kw("twitter", r"[a-z0-9]{35,44}", "a-z0-9"),
           ["twitter"], secret_group_name=SECRET_GROUP),
        _r("typeform-api-token", "Typeform", "Typeform API token", S.MEDIUM,
           ws(r"tfp_[a-z0-9\-_\.=]{59}"), ["tfp_"], secret_group_name=SECRET_GROUP),
        # ----- generic fallbacks ----------------------------------------------
        _r("basic-auth-url", CategoryGeneric, "Credentials embedded in URL", S.HIGH,
           r"[a-zA-Z][a-zA-Z0-9+.\-]{1,9}://[^/\s:@\"']{1,64}:(?P<secret>[^/\s:@\"']{3,64})@"
           r"[0-9a-zA-Z\-_.]{1,128}",
           ["://"], secret_group_name=SECRET_GROUP,
           allow_rules=[
               AllowRule(id="url-placeholder-password",
                         description="templated / placeholder credentials",
                         regex=r"^(?:\$|%s|%v|\{\{|<|\[)"),
           ]),
        _r("generic-api-key", CategoryGeneric, "Generic API key assignment", S.MEDIUM,
           r"(?i)(?:api[_\-]?key|apikey|secret[_\-]?key|auth[_\-]?token|access[_\-]?token)"
           r"[a-z0-9_\-\s\"']{0,10}[=:][\s\"']{0,5}"
           r"(?P<secret>[0-9a-zA-Z_\-]{20,64})(?:[\"'\s]|$)",
           ["api_key", "apikey", "api-key", "secret_key", "secret-key",
            "auth_token", "auth-token", "access_token", "access-token"],
           secret_group_name=SECRET_GROUP,
           allow_rules=[
               AllowRule(id="generic-placeholder",
                         description="placeholder values (matched against the extracted secret)",
                         regex=r"(?i)^(?:x{8,}|\*{8,}|(?:your|my|the|an?|some|this|change|replace|dummy|fake|test|example|sample|placeholder|insert)[_\-]?[a-z_\-]*|[0-9a-zA-Z_\-]*(?:example|sample|placeholder|changeme|xxxxx)[0-9a-zA-Z_\-]*)$"),
           ]),
    ]
    return rules


def builtin_allow_rules() -> list[AllowRule]:
    """Global path allowlist (ref: pkg/fanal/secret/builtin-allow-rules.go:3-65):
    test/example/vendored/system trees where findings are overwhelmingly noise."""
    return [
        AllowRule(id="tests", description="test fixtures",
                  path=r"(?:^|/)(?:tests?|testing|testdata|spec|specs)/"),
        AllowRule(id="examples", description="example code",
                  path=r"(?:^|/)examples?/"),
        AllowRule(id="vendor", description="vendored dependencies",
                  path=r"(?:^|/)(?:vendor|third_party|thirdparty|node_modules)/"),
        AllowRule(id="usr-dirs", description="system binary/library trees",
                  path=r"^usr/(?:share|include|lib)/"),
        AllowRule(id="locale-dir", description="locale data",
                  path=r"(?:^|/)locale/"),
        AllowRule(id="markdown", description="documentation",
                  path=r"\.(?:md|markdown|rst)$"),
        AllowRule(id="golang-dir", description="go module cache",
                  path=r"(?:^|/)go/pkg/mod/"),
        AllowRule(id="python-dist", description="python runtime/dist dirs",
                  path=r"(?:^|/)(?:site-packages|dist-packages|\.venv|venv)/"),
        AllowRule(id="ruby-gems", description="installed ruby gems",
                  path=r"(?:^|/)gems/[^/]+/(?:lib|spec|test)/"),
        AllowRule(id="wordpress-core", description="wordpress core", path=r"(?:^|/)wp-includes/"),
        AllowRule(id="anaconda-dir", description="conda packages", path=r"(?:^|/)pkgs/[^/]+/info/"),
        AllowRule(id="minified-js", description="minified/bundled javascript",
                  path=r"\.(?:min\.js|js\.map)$"),
    ]
